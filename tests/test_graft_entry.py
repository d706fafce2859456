"""The driver's graft entry points must stay importable, jittable, and
sharding-clean on the virtual 8-device mesh (conftest forces CPU x8)."""

import jax

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3  # [B, T, V] logits
    assert jax.numpy.isfinite(out).all()


def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_2():
    # dryrun self-arms a 2-device platform (a real re-arm, exercising the
    # clear-backends path); restore the suite's 8-device mesh afterwards.
    try:
        graft.dryrun_multichip(2)
    finally:
        graft._force_virtual_cpu(8)
    assert len(jax.devices()) == 8
