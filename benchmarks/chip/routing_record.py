"""The step's routing, recorded for a sparse block's reference.

A top-k over router scores flips between the program's bfloat16 step and a
float32 reference wherever the k-th and the next score lie close, and a
flipped expert moves that token's layer output by far more than rounding.
So a sparse block's comparison runs the reference UNDER the step's routing
and checks that routing against the reference's own scores (each block
module states its own limit on what it compares). This file is the part of
that which knows no block: the program's two steps of ``reference.
step_functions`` with the routing output on, the record of what every
position chose, and the way a reference finds its sequence's record.

A block module holds one ``RoutingRecord`` and wraps it:

  step_functions(...)            -> record.step_functions(model_cfg, mesh, ...)
  inside its reference           -> record.chosen_for(tokens, n_routed_layers, k)
  routing_readings(params, dims) -> record.readings(parts, params)
"""

from __future__ import annotations


class RoutingRecord:
    def __init__(self) -> None:
        # One record a row of the last comparison: {"ids" [n], "chosen"
        # [routed layers, n, k]}.
        self.rows: list[dict] = []

    def step_functions(self, model_cfg, mesh, *, B, T, n_pages, page_size, interpret):
        """``reference.step_functions`` with the routing output on: the
        program's dense prefill committed to pages and its paged decode
        through the ragged kernel; what every live position chose in every
        routed layer is recorded by row."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
        from mcpx.engine.paged_decode import decode_chunk_paged
        from mcpx.models.gemma.model import init_kv_cache, prefill

        self.rows.clear()

        @jax.jit
        def prefill_j(params, tokens, lens, table):
            dense = init_kv_cache(model_cfg, B, T)
            last, dense, chosen = prefill(
                params, model_cfg, tokens, lens, dense, last_only=True, routing=True
            )
            pools = init_paged_kv(model_cfg, n_pages, page_size)
            pools = commit_prefill_to_pages(pools, dense, table, lens, page_size)
            return last, pools, chosen

        @jax.jit
        def decode_j(params, tok, pos, table, pools):
            return decode_chunk_paged(
                params, model_cfg, tok[:, None], pos, table, pools,
                use_pallas=True, interpret=interpret, mesh=mesh,
                logits_at=jnp.zeros((B,), jnp.int32), q_lens=jnp.ones((B,), jnp.int32),
                routing=True,
            )

        def sys_prefill(params, tokens, lens, table):
            last, pools, chosen = prefill_j(params, tokens, lens, table)
            chosen, tokens_h = np.asarray(chosen), np.asarray(tokens)  # [L, B, T, k]
            for b, n in enumerate(np.asarray(lens)):
                self.rows.append({"ids": tokens_h[b, :n], "chosen": chosen[:, b, :n]})
            return last, pools

        def sys_decode(params, tok, pos, table, pools):
            logits, pools, chosen = decode_j(params, tok, pos, table, pools)
            chosen, tok_h = np.asarray(chosen), np.asarray(tok)  # [L, B, 1, k]
            for b, rec in enumerate(self.rows):
                rec["ids"] = np.append(rec["ids"], tok_h[b])
                rec["chosen"] = np.concatenate([rec["chosen"], chosen[:, b]], axis=1)
            return logits, pools

        return sys_prefill, sys_decode

    def chosen_for(self, tokens, n_layers: int, k: int, follow: bool = True):
        """The experts the step chose for the sequence whose first tokens are
        a recorded row's, ``[n_layers, T, k]``, -1 at the positions the step
        did not run (and everywhere, for a sequence the step never saw or
        with ``follow`` off). The records enter as constants."""
        import jax.numpy as jnp
        import numpy as np

        T = tokens.shape[0]
        records = [r for r in self.rows if len(r["ids"]) <= T] if follow else []
        if not records:
            return jnp.full((n_layers, T, k), -1, jnp.int32)
        ids = np.full((len(records), T), -1, np.int32)
        chosen = np.full((len(records), n_layers, T, k), -1, np.int32)
        for r, rec in enumerate(records):
            n = len(rec["ids"])
            ids[r, :n], chosen[r, :, :n] = rec["ids"], rec["chosen"]
        n = jnp.asarray([len(rec["ids"]) for rec in records], jnp.int32)
        same = jnp.all((tokens[None, :] == ids) | (jnp.arange(T)[None, :] >= n[:, None]), axis=1)
        score = jnp.where(same, n, -1)  # the longest recorded prefix of these tokens
        best = jnp.argmax(score)
        return jnp.where(score[best] > 0, jnp.asarray(chosen)[best], -1)

    def readings(self, parts, params) -> list[dict]:
        """What the routing check reads on each recorded row (the positions
        the last step ran): ``parts(params, tokens) -> (largest distance, the
        (layer, position) pairs where the reference's own top-k is another
        set, the pairs checked)``."""
        import jax
        import jax.numpy as jnp

        out = []
        for rec in list(self.rows):
            distance, flipped, checked = (
                float(x) for x in jax.jit(parts)(params, jnp.asarray(rec["ids"]))
            )
            out.append({"distance": distance, "flipped": int(flipped), "checked": int(checked)})
        return out
