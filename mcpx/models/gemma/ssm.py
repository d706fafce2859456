"""The Mamba-2 mixer of a ``layer_pattern`` model (``GemmaConfig.hybrid``).

One layer, on its normed input ``n`` [B, T, D] (H heads of P, G groups of N
state, ``inner = H P``, ``C = inner + 2 G N``):

  [z | xBC | dt] = n W_in                    widths inner | C | H
  xBC   = silu(conv(xBC))                    causal, depthwise, K taps, bias
  x, B, C = xBC split [H, P] | [G, N] | [G, N];  head h reads group h // (H/G)
  dt    = softplus(dt + dt_bias)             float32; A = -exp(A_log), a head
  h_t   = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        state [N, H, P] float32
  y_t   = h_t C_t + D_skip x_t
  out   = RMSNorm_groups(y * silu(z)) W_out  the mean square over a group's
                                             inner / G values, one gain of inner

What a row keeps between forwards is ``h`` and the convolution's last K - 1
inputs (its TAIL). The recurrence is computed a CHUNK at a time
(``chunk_outputs`` / ``advance_state``: the state-space duality form): inside a chunk of T tokens
``y_t = exp(L_t) h_0 C_t + sum_{s<=t} (C_t . B_s) exp(L_t - L_s) dt_s x_s``
with ``L`` the running sum of ``dt A``, and the chunk hands on ``h_T =
exp(L_T) h_0 + sum_s exp(L_T - L_s) dt_s x_s (x) B_s``. A position whose
``dt`` is 0 decays nothing and adds nothing: that is how a pad slot, a dead
window slot and a rejected proposal leave the state alone.

The dense prefill scans chunks of ``ssm_chunk_size`` from a zero state
(``mamba_prefill``). The paged forward's window (``mamba_window``) is ONE
chunk from the row's stored state, and it does not commit: a decode window is
``[cur, proposals]`` and how many of its tokens the row keeps is decided
AFTER the forward, so a forward leaves its window's small tensors (``dt``,
the convolution's inputs and outputs) in the state pool as PENDING, the
caller writes how many tokens of it were kept (``state["n"]``), and the NEXT
forward's read of the state applies exactly those (``1 + accepted``) before
it computes anything: the state is read once and written once a layer a
forward, and never holds a rejected token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mcpx.models.gemma.config import GemmaConfig

HIGHEST = lax.Precision.HIGHEST


def split_xbc(xbc: jax.Array, cfg: GemmaConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """[..., C] -> x [..., H, P], B and C [..., G, N]."""
    inner, G, N = cfg.mamba_inner, cfg.mamba_n_groups, cfg.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :inner].reshape(lead + (cfg.mamba_n_heads, cfg.mamba_head_dim))
    b = xbc[..., inner : inner + G * N].reshape(lead + (G, N))
    c = xbc[..., inner + G * N :].reshape(lead + (G, N))
    return x, b, c


def mamba_inputs(n: jax.Array, lp: dict, cfg: GemmaConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """n [B, T, D] -> z [B, T, inner], the convolution's input [B, T, C]
    (both the activations' type) and dt [B, T, H] float32 after its
    softplus."""
    inner, C = cfg.mamba_inner, cfg.conv_width
    zxd = jnp.einsum("btd,de->bte", n, lp["w_in"], preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(zxd[..., inner + C :] + lp["dt_bias"].astype(jnp.float32))
    return zxd[..., :inner].astype(n.dtype), zxd[..., inner : inner + C].astype(n.dtype), dt


def causal_conv(pre: jax.Array, tail: jax.Array, lp: dict, dtype) -> jax.Array:
    """``silu(conv(.))`` of the inputs ``pre`` [B, T, C] that follow ``tail``
    [B, K - 1, C]: output t is taps over inputs t - K + 1 .. t. Computed in
    float32, rounded to ``dtype`` once."""
    K = tail.shape[1] + 1
    T = pre.shape[1]
    full = jnp.concatenate([tail, pre], axis=1).astype(jnp.float32)
    w = lp["conv_w"].astype(jnp.float32)  # [C, K]
    out = lp["conv_b"].astype(jnp.float32)
    for k in range(K):
        out = out + full[:, k : k + T] * w[:, k]
    return jax.nn.silu(out).astype(dtype)


def tail_at(pre: jax.Array, tail: jax.Array, n: jax.Array) -> jax.Array:
    """The convolution's tail after ``n`` [B] of the inputs ``pre`` that
    follow ``tail``: the K - 1 inputs before position n."""
    K1 = tail.shape[1]
    full = jnp.concatenate([tail, pre.astype(tail.dtype)], axis=1)
    idx = n[:, None] + jnp.arange(K1, dtype=n.dtype)[None, :]
    return jnp.take_along_axis(full, idx[:, :, None], axis=1)


def _log_decay(dt: jax.Array, lp: dict) -> jax.Array:
    """dt [B, T, H] float32 -> the running sum of ``dt A`` [B, T, H]."""
    a = -jnp.exp(lp["A_log"].astype(jnp.float32))
    return jnp.cumsum(dt * a, axis=1)


def commit_terms(dt: jax.Array, x: jax.Array, lp: dict) -> tuple[jax.Array, jax.Array]:
    """What moves a state over a chunk whose dead positions have ``dt`` 0:
    ``total`` [B, H] = exp(L_T), the old state's factor, and ``xs`` [B, T, H,
    P] float32 = exp(L_T - L_s) dt_s x_s, so that ``h_T = total h_0 + sum_s
    xs_s (x) B_s``."""
    L = _log_decay(dt, lp)
    scale = jnp.exp(L[:, -1:, :] - L) * dt  # [B, T, H]
    return jnp.exp(L[:, -1]), x.astype(jnp.float32) * scale[..., None]


def advance_state(h: jax.Array, total: jax.Array, xs: jax.Array, b: jax.Array) -> jax.Array:
    """h [B, N, H, P] float32 (the state's N before the heads: the pool's
    layout, heads x head_dim on the lanes) moved by ``commit_terms``' chunk:
    B [B, T, G, N]."""
    Bsz, T, H, P = xs.shape
    G = b.shape[2]
    xg = xs.reshape(Bsz, T, G, H // G, P)
    add = jnp.einsum("btgn,btgrp->bngrp", b.astype(jnp.float32), xg, precision=HIGHEST)
    return total[:, None, :, None] * h + add.reshape(h.shape)


def state_outputs(h: jax.Array, c: jax.Array) -> jax.Array:
    """``h C_t`` of every position: h [B, N, H, P], C [B, T, G, N] -> [B, T,
    H, P] float32, before the position's own decay."""
    Bsz, N, H, P = h.shape
    G = c.shape[2]
    hg = h.reshape(Bsz, N, G, H // G, P)
    out = jnp.einsum("btgn,bngrp->btgrp", c.astype(jnp.float32), hg, precision=HIGHEST)
    return out.reshape(Bsz, c.shape[1], H, P)


def chunk_outputs(
    hc: jax.Array, dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array, lp: dict
) -> jax.Array:
    """A chunk's ``y`` [B, T, H, P] float32 from ``hc = state_outputs(h_0,
    C)``: the old state's part decayed to each position, the chunk's own
    tokens' part (the quadratic form) and the skip."""
    Bsz, T, H, P = x.shape
    G = b.shape[2]
    f32 = jnp.float32
    L = _log_decay(dt, lp)  # [B, T, H]
    # exp(L_t - L_s) for s <= t, 0 above the diagonal (masked before the exp:
    # L_t - L_s is positive there and may overflow).
    diff = L[:, :, None, :] - L[:, None, :, :]  # [B, t, s, H]
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    # (float32 operands, a linear layer's, are multiplied as float32: the
    # default would round them to bfloat16 on the way in)
    exact = HIGHEST if x.dtype == f32 else None
    cb = jnp.einsum("btgn,bsgn->btsg", c, b, preferred_element_type=f32, precision=exact)  # [B, t, s, G]
    m = decay.reshape(Bsz, T, T, G, H // G) * cb[..., None] * dt.reshape(Bsz, 1, T, G, H // G)
    xg = x.reshape(Bsz, T, G, H // G, P)
    intra = jnp.einsum(
        "btsgr,bsgrp->btgrp", m.astype(x.dtype), xg, preferred_element_type=f32, precision=exact
    )
    skip = x.astype(f32) * lp["D_skip"].astype(f32)[:, None]
    return jnp.exp(L)[..., None] * hc + intra.reshape(Bsz, T, H, P) + skip


def gated_out(y: jax.Array, z: jax.Array, lp: dict, cfg: GemmaConfig) -> jax.Array:
    """y [B, T, H, P] float32, z [B, T, inner] -> the mixer's output [B, T, D]
    as accumulated (float32): the gate, then the norm over each group."""
    Bsz, T = z.shape[:2]
    G = cfg.mamba_n_groups
    g = y.reshape(Bsz, T, -1) * jax.nn.silu(z.astype(jnp.float32))
    gg = g.reshape(Bsz, T, G, -1)
    gg = gg * lax.rsqrt(jnp.mean(jnp.square(gg), axis=-1, keepdims=True) + cfg.norm_eps)
    g = (gg.reshape(Bsz, T, -1) * lp["gate_norm"].astype(jnp.float32)).astype(z.dtype)
    return jnp.einsum("bte,ed->btd", g, lp["w_out"], preferred_element_type=jnp.float32)


def ssd_scan(
    h0: jax.Array, dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array, lp: dict, chunk: int
) -> tuple[jax.Array, jax.Array]:
    """The recurrence over T positions from ``h0``, a chunk at a time under a
    ``lax.scan`` that carries the state -> (y [B, T, H, P] float32, h_T)."""
    T = x.shape[1]
    if T <= chunk:
        y = chunk_outputs(state_outputs(h0, c), dt, x, b, c, lp)
        return y, advance_state(h0, *commit_terms(dt, x, lp), b)
    n = -(-T // chunk)

    def chunks(a):  # [B, T, ...] -> [n, B, chunk, ...]; a position past T has dt 0: it is none
        a = jnp.pad(a, ((0, 0), (0, n * chunk - T)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(a.shape[0], n, chunk, *a.shape[2:]), 1, 0)

    def one(h, xs):
        dt_c, x_c, b_c, c_c = xs
        y = chunk_outputs(state_outputs(h, c_c), dt_c, x_c, b_c, c_c, lp)
        return advance_state(h, *commit_terms(dt_c, x_c, lp), b_c), y

    h, ys = lax.scan(one, h0, (chunks(dt), chunks(x), chunks(b), chunks(c)))
    return jnp.moveaxis(ys, 0, 1).reshape(x.shape[0], n * chunk, *x.shape[2:])[:, :T], h


def mamba_prefill(n: jax.Array, lp: dict, cfg: GemmaConfig, seq_lens: jax.Array) -> tuple:
    """The mixer over a padded prompt from an empty state: n [B, T, D] ->
    (its output [B, T, D] float32, (the state AT ``seq_lens`` [B, N, H, P], the
    tail AT ``seq_lens`` [B, K - 1, C])). A pad position has ``dt`` 0."""
    Bsz, T, _ = n.shape
    z, pre, dt = mamba_inputs(n, lp, cfg)
    dt = jnp.where(jnp.arange(T)[None, :, None] < seq_lens[:, None, None], dt, 0.0)
    tail0 = jnp.zeros((Bsz, cfg.conv_kernel - 1, cfg.conv_width), n.dtype)
    x, b, c = split_xbc(causal_conv(pre, tail0, lp, n.dtype), cfg)
    h0 = jnp.zeros((Bsz, cfg.ssm_state_size, cfg.mamba_n_heads, cfg.mamba_head_dim), jnp.float32)
    y, h = ssd_scan(h0, dt, x, b, c, lp, cfg.ssm_chunk_size)
    return gated_out(y, z, lp, cfg), (h, tail_at(pre, tail0, seq_lens))


def mamba_window(
    n: jax.Array,  # [B, S, D] the window's normed input
    lp: dict,
    cfg: GemmaConfig,
    ssm: jax.Array,  # the state pool's recurrent states [layers, slots, N, H P]: kv_cache.init_state_pool
    layer: int,  # which of them this layer's are
    state: dict,  # this layer's small arrays of the pool, [slots, ...]
    slots: jax.Array,  # [B] each row's slot
    q_lens: jax.Array,  # [B] live window slots (0: an idle row, which changes nothing)
    kept: jax.Array,  # [B] tokens of the PENDING window the row kept (state["n"][slots])
    *,
    kernel=None,  # engine/kernels/ssm.ssm_window, or None: the same in jnp
) -> tuple[jax.Array, dict]:
    """One paged forward's window of a Mamba layer -> (its output [B, S, D]
    float32, the states with this layer's moved, the layer's new small
    arrays). First the pending window's
    ``kept`` tokens are applied to the stored state and tail; the window's
    ``y`` is computed from that; then the window stays PENDING (the caller
    says later how much of it was kept). An idle row's slot is not written.
    A window wider than the pool's pending width has no route: nothing
    serves a recurrent layer a suffix prefill (its rows prefill whole)."""
    Bsz, S, _ = n.shape
    W = state["dt"].shape[1]
    if S > W:
        raise ValueError(f"a window of {S} slots, the state pool keeps {W} pending")
    f32 = jnp.float32
    # A row whose slot is out of range (a cohort's padding row) owns no state:
    # it is idle here, whatever its tokens.
    n_slots = ssm.shape[1]
    q_lens = jnp.where(slots < n_slots, q_lens, 0)
    slots = jnp.minimum(slots, n_slots - 1)
    live = q_lens > 0
    in_window = jnp.arange(S)[None, :] < q_lens[:, None]
    # --- what the row left pending, masked to what it kept
    p_dt = jnp.where(jnp.arange(W)[None, :, None] < kept[:, None, None], state["dt"][slots], 0.0)
    p_x, p_b, _ = split_xbc(state["post"][slots], cfg)
    total, xs = commit_terms(p_dt, p_x, lp)
    tail = tail_at(state["pre"][slots], state["conv"][slots], kept)
    # --- this window
    z, pre, dt = mamba_inputs(n, lp, cfg)
    dt = jnp.where(in_window[:, :, None], dt, 0.0)
    post = causal_conv(pre, tail, lp, n.dtype)
    x, b, c = split_xbc(post, cfg)
    at = jnp.where(live, slots, n_slots)  # an idle row's slot is written nowhere
    H, P = cfg.mamba_n_heads, cfg.mamba_head_dim
    N = cfg.ssm_state_size
    if kernel is None:
        # (the pool holds heads x head_dim merged)
        h = advance_state(ssm[layer, slots].reshape(Bsz, N, H, P), total, xs, p_b)
        hc = state_outputs(h, c)
        ssm = ssm.at[layer, at].set(h.reshape(Bsz, N, H * P), mode="drop")
    else:
        # A head's factor repeated over its lanes, B and C a group's matrix.
        ssm, hc = kernel(
            ssm, layer, slots, q_lens,
            jnp.repeat(total, P, axis=1), xs.reshape(Bsz, W, H * P),
            jnp.transpose(p_b.astype(f32), (0, 2, 3, 1)), jnp.transpose(c.astype(f32), (0, 2, 1, 3)),
        )
        hc = hc.reshape(Bsz, S, H, P)
    y = chunk_outputs(hc, dt, x, b, c, lp)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, W - S)) + ((0, 0),) * (a.ndim - 2))
    new = {
        "conv": state["conv"].at[at].set(tail, mode="drop"),
        "dt": state["dt"].at[at].set(pad(dt), mode="drop"),
        "pre": state["pre"].at[at].set(pad(pre), mode="drop"),
        "post": state["post"].at[at].set(pad(post), mode="drop"),
    }
    return gated_out(y, z, lp, cfg), ssm, new


# ------------------------------------------------------- linear attention
# An ``L`` layer of ``GemmaConfig.mixer_ffn``: per head h of H (d = head_dim)
#
#   q, k = RMSNorm_d(n W_q), RMSNorm_d(n W_k), both rotated;  v = n W_v
#   S_t  = lambda_h S_{t-1} + k_t^T v_t        [d, d] float32, lambda a constant
#   o_t  = (q_t / sqrt(d)) S_t
#   y    = W_o( sigmoid(n W_g) (.) RMSNorm_d(o) )
#
# which IS the recurrence above with one group a head (B = k, C = q / sqrt(d),
# x = v), ``dt`` 1 on a live position and 0 on a dead one, ``A = log
# lambda_h``, no skip, no convolution: the chunked scan, the state's layout
# ([d of k, heads x d of v], the pool's) and the window kernel are the Mamba
# layers'. The pending window holds the window's keys and values.
def _linear_scalars(cfg: GemmaConfig) -> dict:
    """``_log_decay`` / ``chunk_outputs``' per-head scalars for a linear
    layer: ``-exp(A_log) = log lambda_h``, no skip."""
    return {
        "A_log": jnp.log(-jnp.asarray(cfg.linear_decay)),
        "D_skip": jnp.zeros((cfg.n_heads,), jnp.float32),
    }


def linear_inputs(n: jax.Array, lp: dict, cfg: GemmaConfig, positions: jax.Array) -> tuple:
    """n [B, T, D], positions [B, T] -> q (scaled), k, v [B, T, H, d], FLOAT32
    as accumulated: what enters the float32 state is not rounded on the way
    (the window's products are a few MFLOP; the pending window alone keeps
    its keys and values in the activations' type)."""
    from mcpx.models.gemma.model import apply_rope, rms_norm

    H, d = cfg.n_heads, cfg.head_dim
    f32 = jnp.float32
    heads = lambda w: jnp.einsum("btd,de->bte", n, w, preferred_element_type=f32).reshape(
        n.shape[:2] + (H, d)
    )
    q, k = heads(lp["wq"]), heads(lp["wk"])
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps, cfg.norm_plus_one, f32)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps, cfg.norm_plus_one, f32)
    if cfg.rope_full_layers:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q * d**-0.5, k, heads(lp["wv"])


def linear_out(y: jax.Array, n: jax.Array, lp: dict, cfg: GemmaConfig) -> jax.Array:
    """y [B, T, H, d] float32 -> the mixer's output [B, T, D] as accumulated:
    the norm over each head's values, the gate out of the layer's normed
    input, W_o."""
    B, T = y.shape[:2]
    f32 = jnp.float32
    o = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
    o = o.reshape(B, T, -1) * lp["o_norm"].astype(f32)
    if cfg.attn_gate:
        gate = jnp.einsum("btd,de->bte", n, lp["w_attn_gate"], preferred_element_type=f32)
        o = o * jax.nn.sigmoid(gate)
    return jnp.einsum("bte,ed->btd", o.astype(n.dtype), lp["wo"], preferred_element_type=f32)


def linear_prefill(n: jax.Array, lp: dict, cfg: GemmaConfig, seq_lens: jax.Array) -> tuple:
    """A linear layer over a padded prompt from an empty state -> (its output
    [B, T, D] float32, the state AT ``seq_lens`` [B, d, H, d])."""
    Bsz, T, _ = n.shape
    H, d = cfg.n_heads, cfg.head_dim
    positions = jnp.broadcast_to(jnp.arange(T), (Bsz, T))
    q, k, v = linear_inputs(n, lp, cfg, positions)
    dt = jnp.broadcast_to((positions < seq_lens[:, None])[:, :, None], (Bsz, T, H)).astype(jnp.float32)
    h0 = jnp.zeros((Bsz, d, H, d), jnp.float32)
    y, h = ssd_scan(h0, dt, v, k, q, _linear_scalars(cfg), cfg.ssm_chunk_size)
    return linear_out(y, n, lp, cfg), h


def linear_window(
    n: jax.Array,  # [B, S, D] the window's normed input
    lp: dict,
    cfg: GemmaConfig,
    ssm: jax.Array,  # the state pool's states [layers, slots, d, H d]: kv_cache.init_state_pool
    layer: int,
    state: dict,  # this layer's pending window, [slots, ...]: dt, k, v
    src: jax.Array,  # [B] the slot each row's state is READ from (out of range: an empty state)
    dst: jax.Array,  # [B] the slot it is written to (out of range: nowhere)
    q_lens: jax.Array,  # [B] live window slots (0: an idle row, which changes nothing)
    kept: jax.Array,  # [B] tokens of ``src``'s PENDING window that stay
    positions: jax.Array,  # [B, S]
    *,
    commit: bool,  # every live slot of this window stays (a prefill's): nothing is left pending
    kernel=None,  # engine/kernels/ssm.ssm_window (a window that stays pending, src == dst), or None
) -> tuple[jax.Array, jax.Array, dict]:
    """One paged forward's window of a linear layer -> (its output [B, S, D]
    float32, the states with this layer's moved, the layer's new pending
    window). As ``mamba_window``: ``src``'s pending window is applied as far
    as it was kept, the window's outputs are computed from that state. A
    decode window then stays PENDING in ``dst``; a prefill's (``commit``: a
    suffix over a shared head's state, a chunk of a head's build) is scanned
    in chunks and ``dst`` holds the state AT the row's last live slot,
    nothing pending. ``src`` and ``dst`` differ where a row starts from a
    state that is not its own (the head's slot)."""
    Bsz, S, _ = n.shape
    H, d = cfg.n_heads, cfg.head_dim
    W = state["dt"].shape[1]
    if S > W and not commit:
        raise ValueError(f"a window of {S} slots, the state pool keeps {W} pending")
    f32 = jnp.float32
    scalars = _linear_scalars(cfg)
    n_slots = ssm.shape[1]
    q_lens = jnp.where(dst < n_slots, q_lens, 0)
    live = q_lens > 0
    in_window = jnp.arange(S)[None, :] < q_lens[:, None]
    has = src < n_slots
    at_src = jnp.minimum(src, n_slots - 1)
    # --- what the source left pending, masked to what was kept of it
    kept = jnp.where(has, kept, 0)
    p_dt = jnp.where(jnp.arange(W)[None, :, None] < kept[:, None, None], state["dt"][at_src], 0.0)
    p_k, p_v = state["k"][at_src], state["v"][at_src]
    total, xs = commit_terms(p_dt, p_v, scalars)
    # --- this window
    q, k, v = linear_inputs(n, lp, cfg, positions)
    dt = jnp.broadcast_to(in_window[:, :, None], (Bsz, S, H)).astype(f32)
    at = jnp.where(live, dst, n_slots)  # an idle row's slot is written nowhere
    if kernel is not None:  # (a window that commits is handed none)
        ssm, hc = kernel(
            ssm, layer, at_src, q_lens,
            jnp.repeat(total, d, axis=1), xs.reshape(Bsz, W, H * d),
            jnp.transpose(p_k.astype(f32), (0, 2, 3, 1)), jnp.transpose(q.astype(f32), (0, 2, 1, 3)),
        )
        y = chunk_outputs(hc.reshape(Bsz, S, H, d), dt, v, k, q, scalars)
    else:
        h0 = jnp.where(has[:, None, None, None], ssm[layer, at_src].reshape(Bsz, d, H, d), 0.0)
        h = advance_state(h0, total, xs, p_k)
        if commit:
            y, h = ssd_scan(h, dt, v, k, q, scalars, cfg.ssm_chunk_size)
        else:
            y = chunk_outputs(state_outputs(h, q), dt, v, k, q, scalars)
        ssm = ssm.at[layer, at].set(h.reshape(Bsz, d, H * d), mode="drop")
    if commit:
        new = {**state, "dt": state["dt"].at[at].set(0.0, mode="drop")}
    else:
        pad = lambda a: jnp.pad(a, ((0, 0), (0, W - S)) + ((0, 0),) * (a.ndim - 2))
        new = {
            "dt": state["dt"].at[at].set(pad(dt), mode="drop"),
            "k": state["k"].at[at].set(pad(k).astype(state["k"].dtype), mode="drop"),
            "v": state["v"].at[at].set(pad(v).astype(state["v"].dtype), mode="drop"),
        }
    return linear_out(y, n, lp, cfg), ssm, new


# ------------------------------------------------- gated short convolution
# A ``C`` layer of ``GemmaConfig.conv_ffn``, on its normed input n [B, T, D]
# (K = ``conv_kernel`` taps):
#
#   [b | c | x] = n W_in                     W_in [D, 3 D], thirds in that order
#   u_t = b_t (.) x_t
#   v_t = sum_k w[:, k] (.) u_{t - K + 1 + k}      causal, depthwise, no bias,
#                                                   NO activation; u before
#                                                   the sequence is 0
#   y_t = W_out (c_t (.) v_t)
#
# The layer's WHOLE state after token t is its TAIL, the last K - 1 values of
# ``u``.
#
# **The mixer runs in float32 between its two weight matrices.** It is a CUBIC
# form of its input (``b (.) x (.) c``), so a relative error in ``n`` comes out
# three times as large, and in a stack where 8 layers of 10 are this mixer the
# roundings of the usual bfloat16 recipe (``n``, ``u``, ``c (.) v``, each
# rounded where a matmul or the state takes it) carried the step to 0.021-0.023
# of the reference on the chip against the comparison's 0.02 (PERF.md, PR 56: on
# the CPU at 256 wide, those three roundings alone read 0.022-0.026 with every
# other rounding of the step switched off, the rest of the step 0.013-0.016
# without them). So: the layer's norm hands on float32, ``W_in`` and ``W_out``
# read their float32 operand as TWO operands of the weights' type (its rounding
# and what the rounding left, ``dot_split``: twice the multiplier's work, which
# is the precision's price, in the form the product's rows choose), ``u`` and
# the taps stay float32, and the tail (a slot's and a page's:
# ``engine/kv_cache.init_state_pool``) holds ``u`` in float32, as the other
# recurrent states of this repo are held. A value weighs the same whether the
# convolution reads it out of its window or out of a tail.

# Rows of the operand (B x T) up to which a split product STACKS its two halves
# along the rows of ONE product: the weights stream once, which is what such a
# product waits for (a decode window: 8 x 8 rows). Past it the multiplier binds
# and the stacking buys nothing and costs the HBM a [B, 2T, K] operand, a
# [B, 2T, N] float32 result and the pass that adds its halves: the two halves
# are two products, summed where the second accumulates. The multiplier's work
# is the same in both forms (it is the precision's price); the rows in the HBM
# were the form's. Chosen from the chip (TPU v5 lite; PERF.md section 6, PR 59:
# a walk of 16 layers, us a layer, stacked / summed): W_in [2560, 10240] at 64
# rows 120 / 190, at 256 191 / 205, at 512 335 / 341, at 1,024 790 / 633, at
# 4,096 3,523 / 2,430; the convolution block's [2048, 6144] 77 / 117, 122 /
# 131, 193 / 190, 352 / 326, 1,911 / 1,234. At 512 rows the forms tie on the
# large matrices and the stacked one is fewer operations: lfm2's cell, whose
# cohorts of four prefill 512 rows, read 1.5% slower with them summed.
SPLIT_STACK_ROWS = 512


def dot_split(x32: jax.Array, w: jax.Array) -> jax.Array:
    """x32 [B, T, K] float32 times w [K, N] -> [B, T, N] float32 as
    accumulated. Weights narrower than float32 read ``x32`` as two operands of
    their own type, ``hi = round(x32)`` and ``lo = round(x32 - hi)``; what is
    lost of ``x32`` is under 2^-16 of it. ``hi @ w + lo @ w`` in float32, in
    the form the rows choose (``SPLIT_STACK_ROWS``)."""
    f32 = jnp.float32
    product = lambda x: jnp.einsum("bte,ed->btd", x, w, preferred_element_type=f32)
    if w.dtype == f32:
        return product(x32)
    # (an explicit rounding: the TPU compiler drops a cast to bfloat16 and back,
    # ``xla_allow_excess_precision``, and ``lo`` would be all zeros)
    info = jnp.finfo(w.dtype)
    hi32 = lax.reduce_precision(x32, exponent_bits=info.nexp, mantissa_bits=info.nmant)
    hi, lo = hi32.astype(w.dtype), (x32 - hi32).astype(w.dtype)
    B, T, _ = x32.shape
    if B * T > SPLIT_STACK_ROWS:
        # The halves are made ONCE and handed to both products as they are: left
        # to choose, XLA recomputes the operand's producer (a layer's norm, the
        # gate) and the rounding inside each product's prologue, and on a v5e
        # that program's scanned run of layers never came back from the device
        # at 4,096 rows (a cohort of 4 at the 1,024 bucket, of 8 at 512: PERF.md
        # section 6, PR 59); with the operands pinned every bucket runs, at the
        # same speed.
        hi, lo = lax.optimization_barrier((hi, lo))
        return product(hi) + product(lo)
    y = product(jnp.concatenate([hi, lo], axis=1))
    return y[:, :T] + y[:, T:]


def conv_inputs(n: jax.Array, lp: dict) -> tuple[jax.Array, jax.Array]:
    """n [B, T, D] float32 -> (u [B, T, D], c [B, T, D]), float32."""
    D = n.shape[-1]
    bcx = dot_split(n, lp["w_in"])
    return bcx[..., :D] * bcx[..., 2 * D :], bcx[..., D : 2 * D]


def short_conv(u: jax.Array, tail: jax.Array, w: jax.Array) -> jax.Array:
    """The taps ``w`` [D, K] over the inputs ``u`` [B, T, D] that follow
    ``tail`` [B, K - 1, D] -> [B, T, D] float32: output t is taps over inputs
    t - K + 1 .. t."""
    K, T = tail.shape[1] + 1, u.shape[1]
    full = jnp.concatenate([tail, u], axis=1).astype(jnp.float32)
    w = w.astype(jnp.float32)
    out = full[:, 0:T] * w[:, 0]
    for k in range(1, K):
        out = out + full[:, k : k + T] * w[:, k]
    return out


def conv_out(v: jax.Array, c: jax.Array, lp: dict) -> jax.Array:
    """-> the mixer's output [B, T, D] as accumulated (float32)."""
    return dot_split(c * v, lp["w_out"])


def conv_prefill(n: jax.Array, lp: dict, cfg: GemmaConfig, seq_lens: jax.Array) -> tuple:
    """The mixer over a padded prompt from an empty tail: n [B, T, D] -> (its
    output [B, T, D] float32, (the tail AT ``seq_lens`` [B, K - 1, D], every
    position's ``u`` [B, T, D]: what the pages' tails are cut from))."""
    u, c = conv_inputs(n, lp)
    tail0 = jnp.zeros((n.shape[0], cfg.conv_kernel - 1, n.shape[-1]), u.dtype)
    out = conv_out(short_conv(u, tail0, lp["conv_w"]), c, lp)
    return out, (tail_at(u, tail0, seq_lens), u)


def conv_window(
    n: jax.Array,  # [B, S, D] the window's normed input, float32
    lp: dict,
    state: dict,  # this layer's arrays of the pool, [slots, ...]: conv, pre
    slots: jax.Array,  # [B] each row's slot (out of range: a padding row, written nowhere)
    q_lens: jax.Array,  # [B] live window slots (0: an idle row, which changes nothing)
    kept: jax.Array,  # [B] tokens of the PENDING window the row kept (state["n"][slots])
    start: "jax.Array | None" = None,  # [B, K - 1, D]: the tail a PREFILL window starts from
) -> tuple[jax.Array, dict, jax.Array, jax.Array]:
    """One paged forward's window of a ``C`` layer -> (its output [B, S, D]
    float32, the layer's new arrays, the tail the window started from, the
    window's ``u``). A decode window (``start`` None) first moves the slot's
    tail over what the row kept of its pending window (``tail_at``), then
    leaves its own ``u`` pending. A prefill window (a suffix over matched
    pages, a chunk of a head's build) starts from ``start``, a page's tail or
    zeros, and commits: the slot holds the tail AT the row's last live slot,
    nothing pending."""
    S = n.shape[1]
    W = state["pre"].shape[1]
    if S > W and start is None:
        raise ValueError(f"a window of {S} slots, the state pool keeps {W} pending")
    n_slots = state["conv"].shape[0]
    at = jnp.where((q_lens > 0) & (slots < n_slots), slots, n_slots)
    own = jnp.minimum(slots, n_slots - 1)
    u, c = conv_inputs(n, lp)
    if start is None:
        tail = tail_at(state["pre"][own], state["conv"][own], kept)
        new = {
            "conv": state["conv"].at[at].set(tail, mode="drop"),
            "pre": state["pre"].at[at].set(
                jnp.pad(u, ((0, 0), (0, W - S), (0, 0))).astype(state["pre"].dtype), mode="drop"
            ),
        }
    else:
        tail = start.astype(u.dtype)
        new = {**state, "conv": state["conv"].at[at].set(
            tail_at(u, tail, q_lens).astype(state["conv"].dtype), mode="drop"
        )}
    return conv_out(short_conv(u, tail, lp["conv_w"]), c, lp), new, tail, u


# ------------------------------------------------------- the selective scan
# A ``J`` layer of ``GemmaConfig.scan_ffn`` (Mamba-1), on its normed input n
# [B, T, D] (I = ``scan_inner`` channels, N = ``ssm_state_size``, R =
# ``mamba_dt_rank``, K = ``conv_kernel`` taps):
#
#   [x | z] = n W_in                            W_in [D, 2 I], x first
#   x_t = silu(b + sum_k w[:, k] (.) x_{t - K + 1 + k})     ``causal_conv``
#   [r | B | C] = x W_x                         W_x [I, R + 2 N]
#   r, B, C = RMSNorm_R(r), RMSNorm_N(B), RMSNorm_N(C)      a gain each
#   dt  = softplus(r W_dt + b_dt)               [I] float32
#   h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#   y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] x_t[c]        A = -exp(A_log)
#   out = (y (.) silu(z)) W_out
#
# The decay is a value a (state, channel) pair: the weight of token s on
# token t inside a chunk is a [T, T, N] tensor a CHANNEL, so the chunk has no
# matrix form (``chunk_outputs`` / ``advance_state`` above need ONE scalar a
# head) and the recurrence is WALKED, a token at a time, the state resident:
# ``selective_walk`` here (a ``lax.scan`` over time; the CPU's and a mesh's
# route) and ``engine/kernels/selective_scan.py`` on one device. The state is
# kept ``[N, I]`` (the state's N on the sublanes, the channels on the lanes:
# ``A_log`` is stored so too), float32, as ``dt`` and the decay are.
#
# **The mixer runs in float32 between its weight matrices**, as the short
# convolution above and for its reason, only more so: ``y`` is a QUARTIC form of
# ``x`` (``dt(x) x B(x) C(x)``, the three inner norms keeping ``B``, ``C`` and
# ``r`` at unit scale whatever ``W_x`` gives), so a relative error in the
# layer's input comes out about four times as large, and it compounds down a
# stack of 26 such mixers. On the chip, all 28 layers at the published widths
# (PERF.md section 6, PR 58; three seeds): with ``W_in``, ``W_x``, ``W_dt`` and
# ``W_out`` on operands rounded ONCE to bfloat16 the logits' rms distance reads
# 0.0356-0.0369 against ``reference.tol``'s 0.0265, not ``correct`` on any seed
# (``models/jamba.py``'s ``mixer_in_bfloat16`` control); as here 0.0204-0.0227.
# So: the layer's norm hands on float32 (``model.scan_norm``), and those four
# read their float32 operand as TWO operands of the weights' type
# (``dot_split``: twice the multiplier's work, free in a decode window, whose
# weights stream once whatever the rows, and what the precision costs a
# prefill; nothing of twice the rows is written there). The convolution's
# inputs and outputs, a slot's tail and its pending window are float32: a
# value weighs the same whether it is read out of a window or out of the pool.
# The feed-forward behind the mixer is NOT part of this: it reads the stream
# rounded once, as every other block's does (in float32 too the step read
# 0.0018, and the comparison passes without it: what no limit can hold is not
# kept).
#
# The state's rule is the Mamba-2 layers' word for word: a decode window is
# walked from the stored state WITHOUT committing; its ``dt``, ``x``, ``B``
# and the convolution's inputs stay PENDING in the pool, the caller writes how
# many of its tokens the row kept, and the next forward first walks exactly
# those into the state. A position whose ``dt`` is 0 decays nothing and adds
# nothing.
def selective_inputs(n: jax.Array, tail: jax.Array, lp: dict, cfg: GemmaConfig) -> tuple:
    """n [B, T, D] float32 after ``tail`` [B, K - 1, I] -> (z [B, T, I], the
    convolution's inputs [B, T, I], x after it [B, T, I], dt [B, T, I], B and
    C [B, T, N]), all float32."""
    from mcpx.models.gemma.model import rms_norm

    I, N, R = cfg.scan_inner, cfg.ssm_state_size, cfg.mamba_dt_rank
    f32 = jnp.float32
    xz = dot_split(n, lp["w_in"])
    pre, z = xz[..., :I], xz[..., I:]
    x = causal_conv(pre, tail, lp, f32)
    rbc = dot_split(x, lp["w_x"])
    norm = lambda a, gain: rms_norm(a, gain, cfg.norm_eps, False, f32)
    r = norm(rbc[..., :R], lp["dt_norm"])
    b, c = norm(rbc[..., R : R + N], lp["b_norm"]), norm(rbc[..., R + N :], lp["c_norm"])
    dt = jax.nn.softplus(dot_split(r, lp["w_dt"]) + lp["dt_bias"].astype(f32))
    return z, pre, x, dt, b, c


def selective_walk(
    h: jax.Array, dt: jax.Array, x: jax.Array, b: jax.Array, c: "jax.Array | None", a_log: jax.Array
) -> tuple:
    """The recurrence over T positions from ``h`` [B, N, I] float32, a token a
    step: dt and x [B, T, I], B and C [B, T, N], ``a_log`` [N, I] -> (y [B, T,
    I] float32 before the skip, None where ``c`` is, h_T)."""
    f32 = jnp.float32
    a = -jnp.exp(a_log.astype(f32))
    time_major = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)

    def step(h, t):
        dt_t, x_t, b_t = t[:3]
        h = jnp.exp(dt_t[:, None, :] * a) * h + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return h, None if c is None else jnp.sum(h * t[3][:, :, None], axis=1)

    terms = (dt, x, b) if c is None else (dt, x, b, c)
    h, y = lax.scan(step, h, tuple(time_major(t) for t in terms))
    return None if c is None else jnp.moveaxis(y, 0, 1), h


def selective_out(y: jax.Array, x: jax.Array, z: jax.Array, lp: dict) -> jax.Array:
    """y [B, T, I] float32 -> the mixer's output [B, T, D] as accumulated: the
    skip, the gate, ``W_out``."""
    return dot_split((y + lp["D_skip"].astype(jnp.float32) * x) * jax.nn.silu(z), lp["w_out"])


def selective_prefill(
    n: jax.Array, lp: dict, cfg: GemmaConfig, seq_lens: jax.Array, *, kernel=None
) -> tuple:
    """The mixer over a padded prompt from an empty state: n [B, T, D] float32 -> (its
    output [B, T, D] float32, (the state AT ``seq_lens`` [B, N, I], the tail
    AT ``seq_lens`` [B, K - 1, I])). A pad position has ``dt`` 0. ``kernel``:
    ``engine/kernels/selective_scan.selective_scan_prefill``, or None: the
    same walk in jnp."""
    Bsz, T, _ = n.shape
    tail0 = jnp.zeros((Bsz, cfg.conv_kernel - 1, cfg.scan_inner), jnp.float32)
    z, pre, x, dt, b, c = selective_inputs(n, tail0, lp, cfg)
    dt = jnp.where(jnp.arange(T)[None, :, None] < seq_lens[:, None, None], dt, 0.0)
    if kernel is None:
        h0 = jnp.zeros((Bsz, cfg.ssm_state_size, cfg.scan_inner), jnp.float32)
        y, h = selective_walk(h0, dt, x, b, c, lp["A_log"])
    else:
        y, h = kernel(dt, x, b, c, lp["A_log"], seq_lens)
    return selective_out(y, x, z, lp), (h, tail_at(pre, tail0, seq_lens))


def selective_window(
    n: jax.Array,  # [B, S, D] the window's normed input, float32
    lp: dict,
    cfg: GemmaConfig,
    pool: dict,  # the state pool (kv_cache.init_state_pool's fourth kind): every array [J layers, slots, ...]
    layer: jax.Array,  # which J layer this is: a scan's carried number
    slots: jax.Array,  # [B] each row's slot (out of range: a padding row, idle)
    q_lens: jax.Array,  # [B] live window slots (0: an idle row, which changes nothing)
    kept: jax.Array,  # [B] tokens of the PENDING window the row kept (pool["n"][slots])
    *,
    kernel=None,  # engine/kernels/selective_scan.selective_scan_window, or None: the same in jnp
) -> tuple[jax.Array, dict]:
    """One paged forward's window of a ``J`` layer -> (its output [B, S, D]
    float32, the pool with this layer's rows moved). As ``mamba_window``: the
    pending window's ``kept`` tokens are walked into the stored state and the
    tail moved over them, that state is written back, the window's live slots
    are walked from it WITHOUT committing and stay pending. An idle row's
    slot is written nowhere."""
    Bsz, S, _ = n.shape
    W = pool["dt"].shape[2]
    if S > W:
        raise ValueError(f"a window of {S} slots, the state pool keeps {W} pending")
    n_slots = pool["ssm"].shape[1]
    q_lens = jnp.where(slots < n_slots, q_lens, 0)
    own = jnp.minimum(slots, n_slots - 1)
    in_window = jnp.arange(S)[None, :] < q_lens[:, None]
    mine = lambda name: pool[name][layer, own]
    # --- what the row left pending, masked to what it kept
    p_dt = jnp.where(jnp.arange(W)[None, :, None] < kept[:, None, None], mine("dt"), 0.0)
    p_x, p_b = mine("x"), mine("b")
    tail = tail_at(mine("pre"), mine("conv"), kept)
    # --- this window
    z, pre, x, dt, b, c = selective_inputs(n, tail, lp, cfg)
    dt = jnp.where(in_window[:, :, None], dt, 0.0)
    at = jnp.where(q_lens > 0, slots, n_slots)  # an idle row's slot is written nowhere
    if kernel is None:
        _, h = selective_walk(pool["ssm"][layer, own], p_dt, p_x, p_b, None, lp["A_log"])
        ssm = pool["ssm"].at[layer, at].set(h, mode="drop")
        y, _ = selective_walk(h, dt, x, b, c, lp["A_log"])
    else:
        ssm, y = kernel(
            pool["ssm"], layer, own, q_lens, p_dt, p_x, p_b, dt, x, b, c, lp["A_log"]
        )
    pad = lambda a: jnp.pad(a, ((0, 0), (0, W - S), (0, 0)))
    put = lambda name, new: pool[name].at[layer, at].set(new, mode="drop")
    new = {
        **pool, "ssm": ssm, "conv": put("conv", tail), "dt": put("dt", pad(dt)),
        "pre": put("pre", pad(pre)), "x": put("x", pad(x)), "b": put("b", pad(b)),
    }
    return selective_out(y, x, z, lp), new
