"""Gemma-architecture configuration.

Architecture follows the public Gemma family (RMSNorm with +1 scale, RoPE,
GQA/MQA attention, GeGLU MLP, tied embeddings, embedding scaling by
sqrt(d_model)) — re-implemented TPU-first; the reference framework has no
model code at all (its LLM is OpenAI's API, reference
``control_plane.py:69-73``).

Size presets carry the *architecture dims* of Gemma-2B/7B; ``vocab_size`` is
independent so the in-tree byte tokenizer (384) and real SentencePiece
checkpoints (256128) both fit the same code path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from mcpx.core.errors import ConfigError

# A full layer's window in the layer scan's data: past any context.
NO_WINDOW = 2**30


FULL, SLIDING = "full_attention", "sliding_attention"


@dataclass(frozen=True)
class GemmaConfig:
    """What a decoder layer IS. The defaults are the Gemma block (tanh-GELU
    gate, tied embeddings scaled by sqrt(d_model), a (1 + scale) norm gain,
    full causal attention in every layer, one rope, a dense feed-forward);
    the further fields say where another model's block departs from it, so
    one forward serves both and a configuration at the defaults traces to
    the program it always did."""

    vocab_size: int = 384
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 32
    d_ff: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    # --- the block's pointwise choices
    activation: str = "gelu_tanh"  # or "silu" (SwiGLU)
    tie_embeddings: bool = True  # False: the output head is its own [D, V] leaf
    scale_embeddings: bool = True  # embeddings times sqrt(d_model)
    norm_plus_one: bool = True  # gain (1 + scale), scale drawn 0; False: gain g, drawn 1
    # --- attention kinds: one entry per layer, () = every layer full.
    # A sliding layer's query sees itself and the ``sliding_window - 1``
    # keys before it, and rotates with ``rope_theta`` as it stands; a full
    # layer's rope is YaRN-stretched where ``yarn_factor`` > 0.
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    # ``rope_full_layers`` False: a full layer's q and k are not rotated (its
    # row of ``rope_tables`` is zeros, the identity rotation).
    rope_full_layers: bool = True
    # --- attention's further parts: an RMSNorm over head_dim of q and of k
    # (one gain of head_dim each) before the rope and the KV write; an
    # output gate, ``sigmoid(Wg n1)`` on the attention's result before Wo;
    # a norm on each branch's OUTPUT before it joins the residual.
    qk_norm: bool = False
    attn_gate: bool = False
    post_norms: bool = False
    # --- sparse feed-forward: ``n_experts`` > 0 replaces the dense MLP, in
    # every layer after the ``n_dense_layers`` leading ones, by a router
    # ``n_experts`` wide choosing ``n_experts_per_tok`` experts of width
    # ``d_expert``. This device holds ``experts_held`` of them from
    # ``expert_first`` on (0 = all): it routes over all and computes its own
    # experts' part of the result.
    n_experts: int = 0
    n_experts_per_tok: int = 0
    d_expert: int = 0
    expert_first: int = 0
    experts_held: int = 0
    n_dense_layers: int = 0
    # A shared expert of this width beside the routed ones (0: none): every
    # token reads it, weight 1.
    d_shared_expert: int = 0
    # The router's scoring: ``softmax`` over all experts before the choice,
    # the chosen renormalised; or ``sigmoid`` scores, chosen by score plus a
    # per-expert bias where ``router_bias_scale`` > 0 (the bias enters the
    # CHOICE, never the weights; a random init draws it N(0, scale^2)), the
    # chosen scores renormalised, times ``router_scale``.
    router_scoring: str = "softmax"
    router_bias_scale: float = 0.0
    router_scale: float = 1.0
    # Group-limited choice (sigmoid scoring; 0 = none): the experts lie in
    # ``router_groups`` runs of consecutive ones, a group scores the sum of
    # its two largest (biased) scores, the best ``router_groups_kept`` groups
    # stay and the choice is made inside them. The weights are the unbiased
    # scores, as without groups.
    router_groups: int = 0
    router_groups_kept: int = 0
    # What the sigmoid router adds to the chosen scores' sum before it
    # divides by it.
    router_norm_eps: float = 1e-20
    # --- the attention kind: ``heads`` (MHA / GQA / MQA: ``n_kv_heads`` heads
    # of K and of V a token in the cache) or ``latent``: the query through a
    # rank-``q_lora_rank`` bottleneck with its own norm, keys and values through
    # ONE normed latent of ``kv_lora_rank`` a token beside ONE rotated key of
    # ``qk_rope_head_dim`` that every head shares; the cache holds those two
    # and no head. A head's query and key are ``head_dim`` unrotated values
    # (from the latent) and the ``qk_rope_head_dim`` rotated ones, its value
    # ``v_head_dim`` wide. ``attn_score_factor`` multiplies the softmax scale
    # (YaRN's m^2 where a family applies it there and not to cos and sin).
    attention: str = "heads"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    attn_score_factor: float = 1.0
    # --- a learned index over a latent cache (``index_topk`` > 0; 0 = none,
    # and the block every latent configuration was before): ``index_n_heads``
    # index queries of ``index_head_dim`` a token (out of the query's latent)
    # score every cached token's ONE index key (LayerNorm of its own
    # projection, its first ``qk_rope_head_dim`` values rotated), ``sum_h w_h
    # relu(q_h . k)`` with per-head weights out of the token's normed input,
    # and the attention reads the ``index_topk`` best-scoring tokens a query
    # can see and no others. The index key lives in the rotated key's page
    # row, behind its lanes (``kv_widths``, ``index_key_offset``).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # --- a layer that is ONE thing alone (``layer_pattern`` non-empty; empty =
    # every block above, attention followed by a feed-forward): one letter a
    # layer, ``M`` a Mamba-2 mixer, ``E`` a feed-forward (the sparse one above),
    # ``*`` attention (heads, unrotated), each ``x + f(norm(x))`` with one norm.
    # The Mamba widths: ``mamba_n_heads`` heads of ``mamba_head_dim``, B and C
    # in ``mamba_n_groups`` groups of ``ssm_state_size``, a causal depthwise
    # convolution of ``conv_kernel`` taps over x, B and C, the scan in chunks
    # of ``ssm_chunk_size``; ``time_step_*`` say how a random ``dt_bias`` is
    # drawn. The recurrent state a row holds a Mamba layer is ``[heads,
    # head_dim, state]`` float32 beside the convolution's last ``conv_kernel
    # - 1`` inputs: no pages (``engine/kv_cache.init_state_pool``).
    layer_pattern: str = ""
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    ssm_state_size: int = 0
    conv_kernel: int = 4
    ssm_chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # The routed experts work in a latent of this width (0: on the model's
    # own): the layer projects its normed input down once, an expert is TWO
    # matrices (``act(l U) V``, no gate), the weighted sum is taken in the
    # latent and projected up once. The router and the shared expert read the
    # full width.
    moe_latent_size: int = 0
    # --- a layer that is a MIXER followed by the dense gated feed-forward
    # (``layer_pattern`` of ``L`` and ``S`` alone; two norms a layer), the
    # mixer one of two, both ``n_heads`` heads of ``head_dim`` behind an
    # output gate ``sigmoid(Wg n)``: ``L`` LINEAR attention, one key and one
    # query a head, q and k normed per head and rotated, a constant decay a
    # head (``linear_decay``), the state a row ``[head_dim, n_heads x
    # head_dim]`` float32 and an RMSNorm over each head's output
    # (``models/gemma/ssm.py``); ``S`` attention on ``n_kv_heads`` unrotated KV
    # heads that reads, a (query, KV head), the ``block_topk`` best of the
    # context's blocks of ``block_size`` tokens by a parameter-free score over
    # POOLED keys (the mean of ``2 x pool_stride`` keys every ``pool_stride``),
    # block 0..``block_init`` - 1 and the blocks reaching back
    # ``block_window`` tokens always among them (``models/gemma/sparse.py``).
    # Three scales of the family: ``embed_scale`` on the embedding (0: none),
    # ``residual_scale`` on every branch before it joins, ``logit_divisor``
    # on the final normed state before the head.
    block_size: int = 0
    block_topk: int = 0
    block_init: int = 1
    block_window: int = 0
    pool_stride: int = 16
    embed_scale: float = 0.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # --- a layer that is a MIXER followed by a feed-forward that is DENSE in
    # the ``n_dense_layers`` leading layers and the ROUTED one after them
    # (``layer_pattern`` of ``C`` and ``A`` alone; two norms a layer, plain
    # gains): ``C`` a gated SHORT CONVOLUTION, ``[b | c | x] = n W_in``, ``u =
    # b (.) x``, ``v`` the causal depthwise convolution of ``u`` over
    # ``conv_kernel`` taps (no bias, no activation), ``y = W_out (c (.) v)``:
    # its whole state is the last ``conv_kernel - 1`` values of ``u``, which
    # the state pool keeps a slot AND a page (``engine/kv_cache.
    # init_state_pool``); ``A`` rotated GQA, q and k normed per head first
    # (``models/gemma/ssm.py``, ``model.py``). A ``head_dim`` of 64 lies two
    # KV heads to a 128-lane row of the pools (``kv_pack``).
    # --- a layer that is a MIXER followed by the dense gated feed-forward, its
    # walk SCANNED over each run of like layers (``layer_pattern`` of ``J`` and
    # ``Q`` alone; two norms a layer, plain gains, a float32 residual stream,
    # no position encoding anywhere): ``J`` a Mamba-1 SELECTIVE SCAN, ``[x |
    # z] = n W_in`` (``inner = mamba_expand x d_model`` each), ``x`` through a
    # causal depthwise convolution of ``conv_kernel`` taps with bias and a
    # silu, ``[r | B | C] = x W_x`` (``mamba_dt_rank | ssm_state_size |
    # ssm_state_size``, each RMS-normed under its own gain), ``dt =
    # softplus(r W_dt + b_dt)``, and the recurrence ``h_t[n, c] = exp(dt_t[c]
    # A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]``, ``y_t[c] = sum_n C_t[n]
    # h_t[n, c] + D[c] x_t[c]``, ``out = (y (.) silu(z)) W_out``: the decay is a
    # value a (state, channel) pair, so a chunk has no matrix form and the
    # recurrence is WALKED (``models/gemma/ssm.py``, ``engine/kernels/
    # selective_scan.py``); the state a row ``[ssm_state_size, inner]`` float32
    # beside the convolution's last ``conv_kernel - 1`` inputs; ``Q`` unrotated
    # attention, ``n_heads`` query heads on ``n_kv_heads`` KV heads (1: MQA).
    # The two other alphabets of mixer + feed-forward layers keep their walks
    # unrolled over a tuple of per-layer pool dicts; this one's pool arrays
    # are STACKED a layer so a scan can index them, which is why it is an
    # alphabet of its own and not two more letters of ``L`` / ``S``.
    mamba_expand: int = 0
    mamba_dt_rank: int = 0

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads != 0:
            raise ConfigError("n_heads must be divisible by n_kv_heads")
        if self.attention not in ("heads", "latent"):
            raise ConfigError(f"attention {self.attention!r}: heads or latent")
        latent_sizes = (self.q_lora_rank, self.kv_lora_rank, self.qk_rope_head_dim, self.v_head_dim)
        if self.latent:
            if min(latent_sizes) < 1 or self.qk_rope_head_dim % 2:
                raise ConfigError(
                    "latent attention needs q_lora_rank, kv_lora_rank, v_head_dim >= 1 and an "
                    "even qk_rope_head_dim"
                )
            if self.n_kv_heads != 1:
                raise ConfigError("latent attention caches one latent a token: n_kv_heads is 1")
            if self.qk_norm or self.attn_gate or self.layer_types:
                raise ConfigError(
                    "latent attention has its own two norms, no output gate and no window layers"
                )
        elif any(latent_sizes) or self.attn_score_factor != 1.0:
            raise ConfigError("the latent ranks and attn_score_factor belong to attention='latent'")
        index_sizes = (self.index_n_heads, self.index_head_dim, self.index_topk)
        if any(index_sizes) and (
            not self.latent or min(index_sizes) < 1 or self.index_head_dim < self.qk_rope_head_dim
        ):
            raise ConfigError(
                "the index (index_n_heads, index_head_dim >= qk_rope_head_dim, index_topk, all "
                ">= 1) belongs to attention='latent'"
            )
        if self.activation not in ("gelu_tanh", "silu", "relu2"):
            raise ConfigError(f"activation {self.activation!r}: gelu_tanh, silu or relu2")
        if self.mixer_ffn:
            if set(self.layer_pattern) - set("LS") or len(self.layer_pattern) != self.n_layers:
                raise ConfigError("layer_pattern: L and S alone (a mixer + feed-forward model), one a layer")
            if "S" in self.layer_pattern and (
                min(self.block_size, self.block_topk, self.block_init, self.pool_stride) < 1
                or self.block_size % self.pool_stride or self.block_window % self.block_size
            ):
                raise ConfigError(
                    "an S layer needs block_size (a multiple of pool_stride), block_topk, "
                    "block_init >= 1 and block_window a multiple of block_size"
                )
            if self.latent or self.layer_types or self.n_experts or self.post_norms \
                    or self.norm_plus_one or self.mamba_n_heads or self.head_dim % 2:
                raise ConfigError(
                    "an L/S layer_pattern is heads attention with plain norm gains, a dense "
                    "feed-forward and no Mamba widths"
                )
        elif self.conv_ffn:
            if len(self.layer_pattern) != self.n_layers or self.conv_kernel < 2:
                raise ConfigError("layer_pattern: C and A alone, one a layer, and conv_kernel >= 2")
            if self.latent or self.layer_types or self.post_norms or self.attn_gate \
                    or self.norm_plus_one or self.mamba_n_heads or self.d_shared_expert \
                    or self.moe_latent_size or not self.rope_full_layers or self.yarn_factor \
                    or self.head_dim % 2 or self.activation == "relu2":
                raise ConfigError(
                    "a C/A layer_pattern is heads attention, rotated, with plain norm gains, no "
                    "gate, window, second norm, shared expert, latent or Mamba widths"
                )
        elif self.scan_ffn:
            if len(self.layer_pattern) != self.n_layers:
                raise ConfigError("layer_pattern: J and Q alone, one a layer")
            sizes = (self.mamba_expand, self.mamba_dt_rank, self.ssm_state_size)
            if "J" in self.layer_pattern and (min(sizes) < 1 or self.conv_kernel < 2):
                raise ConfigError(
                    "a J layer needs mamba_expand, mamba_dt_rank, ssm_state_size >= 1 and "
                    "conv_kernel >= 2"
                )
            if self.latent or self.layer_types or self.n_experts or self.post_norms \
                    or self.attn_gate or self.qk_norm or self.norm_plus_one or self.mamba_n_heads \
                    or self.rope_full_layers or self.scale_embeddings or self.activation == "relu2":
                raise ConfigError(
                    "a J/Q layer_pattern is heads attention, unrotated (rope_full_layers off), "
                    "with plain norm gains, unscaled embeddings, a dense feed-forward and no "
                    "gate, q/k norm, window, second norm, experts, latent or Mamba-2 widths"
                )
        elif self.hybrid:
            if set(self.layer_pattern) - set("ME*") or len(self.layer_pattern) != self.n_layers:
                raise ConfigError("layer_pattern: one of M, E, * for each of n_layers layers")
            sizes = (self.mamba_n_heads, self.mamba_head_dim, self.mamba_n_groups, self.ssm_state_size)
            if "M" in self.layer_pattern and (
                min(sizes) < 1 or self.mamba_n_heads % self.mamba_n_groups or self.conv_kernel < 2
                or self.mamba_inner % self.mamba_n_groups
            ):
                raise ConfigError(
                    "a Mamba layer needs mamba_n_heads (a multiple of mamba_n_groups), "
                    "mamba_head_dim, ssm_state_size >= 1 and conv_kernel >= 2"
                )
            if "E" not in self.layer_pattern or not (self.n_experts and self.d_shared_expert):
                raise ConfigError(
                    "a layer_pattern has an E layer, the sparse feed-forward beside its shared "
                    "expert (the segment's counters ride on the sparse layers')"
                )
            if self.latent or self.layer_types or self.n_dense_layers or self.qk_norm \
                    or self.attn_gate or self.post_norms or self.rope_full_layers:
                raise ConfigError(
                    "a layer_pattern's attention is plain heads, unrotated "
                    "(rope_full_layers off), with no window, gate, q/k norm or second norm"
                )
        elif self.moe_latent_size or self.mamba_n_heads or self.activation == "relu2":
            raise ConfigError("the Mamba widths, moe_latent_size and relu2 belong to a layer_pattern")
        if (self.mamba_expand or self.mamba_dt_rank) and not self.scan_ffn:
            raise ConfigError("mamba_expand and mamba_dt_rank belong to a J/Q layer_pattern")
        if not self.mixer_ffn and (
            self.block_size or self.block_topk or self.block_window or self.embed_scale
            or self.residual_scale != 1.0 or self.logit_divisor != 1.0
        ):
            raise ConfigError("the block selection and the three scales belong to an L/S layer_pattern")
        # A JSON round trip (dataclasses.asdict -> GemmaConfig(**d)) hands a list.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.n_layers:
                raise ConfigError(
                    f"layer_types has {len(self.layer_types)} entries for {self.n_layers} layers"
                )
            unknown = set(self.layer_types) - {FULL, SLIDING}
            if unknown:
                raise ConfigError(f"layer_types: unknown kind(s) {sorted(unknown)}")
            if SLIDING in self.layer_types and self.sliding_window < 1:
                raise ConfigError("sliding_attention layers need sliding_window >= 1")
        if self.yarn_factor and self.yarn_original_max_pos < 1:
            raise ConfigError("yarn_factor needs yarn_original_max_pos")
        if self.n_experts:
            if not 1 <= self.n_experts_per_tok <= self.n_experts or self.d_expert < 1:
                raise ConfigError(
                    "n_experts needs 1 <= n_experts_per_tok <= n_experts and d_expert >= 1"
                )
            if self.expert_first < 0 or self.expert_first + self.n_experts_held > self.n_experts:
                raise ConfigError(
                    f"experts {self.expert_first}..{self.expert_first + self.n_experts_held} "
                    f"are not among the router's {self.n_experts}"
                )
            if self.hybrid and not self.conv_ffn and "E" not in self.layer_pattern:
                raise ConfigError("n_experts needs an E layer in layer_pattern")
            if not 0 <= self.n_dense_layers < self.n_layers:
                raise ConfigError("n_dense_layers leaves no sparse layer (or is negative)")
            if self.router_scoring not in ("softmax", "sigmoid"):
                raise ConfigError(f"router_scoring {self.router_scoring!r}: softmax or sigmoid")
            if self.router_scoring == "softmax" and (
                self.router_bias_scale or self.router_scale != 1.0 or self.router_groups
            ):
                raise ConfigError("router bias, scale and groups belong to sigmoid scoring")
            if self.router_groups:
                size = self.n_experts // self.router_groups
                if (
                    self.n_experts % self.router_groups
                    or size < 2
                    or not 1 <= self.router_groups_kept <= self.router_groups
                    or self.router_groups_kept * size < self.n_experts_per_tok
                ):
                    raise ConfigError(
                        "router_groups divides n_experts into groups of >= 2, of which "
                        "router_groups_kept (1..router_groups) hold n_experts_per_tok"
                    )
            elif self.router_groups_kept:
                raise ConfigError("router_groups_kept needs router_groups")
        elif self.n_dense_layers or self.d_shared_expert:
            raise ConfigError("n_dense_layers / d_shared_expert need n_experts")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def hybrid(self) -> bool:
        """A ``layer_pattern`` model: its layers are walked one by one, and
        some keep a recurrent state a row beside the pages."""
        return bool(self.layer_pattern)

    @property
    def mixer_ffn(self) -> bool:
        """A ``layer_pattern`` of ``L`` / ``S``: every layer a mixer followed
        by the dense feed-forward (the other patterns' layers are one thing
        alone)."""
        return bool(self.layer_pattern) and not set(self.layer_pattern) - set("LS")

    @property
    def conv_ffn(self) -> bool:
        """A ``layer_pattern`` of ``C`` / ``A``: every layer a mixer (a gated
        short convolution, or rotated attention) followed by a feed-forward,
        dense in the leading layers and routed after them."""
        return bool(self.layer_pattern) and not set(self.layer_pattern) - set("CA")

    @property
    def scan_ffn(self) -> bool:
        """A ``layer_pattern`` of ``J`` / ``Q``: every layer a mixer (a Mamba-1
        selective scan, or unrotated attention) followed by the dense
        feed-forward, the walk scanned over each run of like layers."""
        return bool(self.layer_pattern) and not set(self.layer_pattern) - set("JQ")

    @property
    def n_scan_layers(self) -> int:
        return self.layer_pattern.count("J") if self.scan_ffn else 0

    @property
    def scan_inner(self) -> int:
        """A ``J`` mixer's inner width: the channels of its recurrence."""
        return self.mamba_expand * self.d_model

    @property
    def dense_pattern(self) -> bool:
        """A ``layer_pattern`` with no routed expert anywhere (``L`` / ``S``,
        ``J`` / ``Q``): a forward reads every leaf whole, and its counters are
        the forward's own alone."""
        return self.mixer_ffn or self.scan_ffn

    @property
    def n_conv_layers(self) -> int:
        return self.layer_pattern.count("C") if self.conv_ffn else 0

    @property
    def page_state(self) -> bool:
        """Whether every recurrent layer's WHOLE state is small enough to be
        kept at every page boundary, a row a page beside the page's keys
        (``init_state_pool``'s ``tails``): a short convolution's last
        ``conv_kernel - 1`` inputs. A row of such a model matches the radix
        tree at ANY depth and starts from the tail of its last matched page."""
        return self.n_conv_layers > 0

    @property
    def suffix_route(self) -> bool:
        """Whether a row may start from pages the radix tree holds: a model
        with no recurrent layer, one whose state is page-addressable
        (``page_state``), or one that keeps a declared head's end state
        (``head_state``, at that head's length alone). Else its rows prefill
        whole."""
        return not self.hybrid or self.head_state or self.page_state

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_pattern.count("M")

    @property
    def n_linear_layers(self) -> int:
        return self.layer_pattern.count("L") if self.mixer_ffn else 0

    @property
    def n_recurrent_layers(self) -> int:
        """Layers that keep a state a row in the state pool."""
        return self.n_mamba_layers + self.n_linear_layers + self.n_conv_layers + self.n_scan_layers

    @property
    def n_block_layers(self) -> int:
        """Layers whose attention selects key blocks (``S``): the key-sum
        pool's layer axis, which is the page pools' too."""
        return self.layer_pattern.count("S") if self.mixer_ffn else 0

    @property
    def head_state(self) -> bool:
        """Whether the engine keeps the END STATE of a declared shared head
        in a slot of its own and hands a copy to every row that matches it:
        where every recurrent layer is linear attention. (A Mamba layer's
        state and tail are kept a slot alone and have no suffix route: such a
        model's rows prefill whole. A short convolution's tail is kept a
        PAGE, ``page_state``, and needs no head slot.)"""
        return self.n_linear_layers > 0

    @property
    def linear_decay(self) -> "np.ndarray":
        """``log lambda_h`` [n_heads] float32 of the linear layers, the same
        in every layer: ``lambda_h = exp(-2^(-8 (h + 1) / n_heads))``."""
        h = np.arange(1, self.n_heads + 1, dtype=np.float64)
        return (-(2.0 ** (-8.0 * h / self.n_heads))).astype(np.float32)

    @property
    def blocks_kept(self) -> int:
        """The trailing blocks every query keeps: ``block_window`` tokens' worth."""
        return self.block_window // self.block_size if self.block_size else 0

    @property
    def n_attn_layers(self) -> int:
        """Layers that cache keys and values: the page pools' layer axis."""
        if self.mixer_ffn:
            return self.layer_pattern.count("S")
        if self.conv_ffn:
            return self.layer_pattern.count("A")
        if self.scan_ffn:
            return self.layer_pattern.count("Q")
        return self.layer_pattern.count("*") if self.hybrid else self.n_layers

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """What the convolution runs over: x, then B and C of every group."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.ssm_state_size

    @property
    def ssm_slot_bytes(self) -> int:
        """Bytes of ONE recurrent layer's state of one row (float32)."""
        if self.mixer_ffn:
            return self.n_heads * self.head_dim * self.head_dim * 4
        if self.conv_ffn:
            return 0  # no recurrent state array: ``conv_tail_bytes``
        if self.scan_ffn:
            return self.scan_inner * self.ssm_state_size * 4
        return self.mamba_inner * self.ssm_state_size * 4

    @property
    def conv_tail_bytes(self) -> int:
        """Bytes of ONE short-convolution layer's tail, of a row or of a page
        (float32: the mixer's own precision, ``models/gemma/ssm.py``)."""
        return (self.conv_kernel - 1) * self.d_model * 4

    @property
    def kv_pack(self) -> int:
        """KV heads that share a 128-lane row of the page pools: 2 for a
        ``C`` / ``A`` pattern's heads of 64 (an even number of them), else 1.
        A packed pool is ``[n_kv_heads / kv_pack, layers, pages, page_size,
        kv_pack x head_dim]``: the ragged kernel then multiplies whole lane
        widths, each query head padded with zeros over its row-mate's lanes
        (``engine/paged_decode._packed_attend``), and no byte of the pool is
        padding."""
        if self.conv_ffn and self.head_dim < 128 and 128 % self.head_dim == 0:
            pack = 128 // self.head_dim
            if self.n_kv_heads % pack == 0:
                return pack
        return 1

    @property
    def latent(self) -> bool:
        return self.attention == "latent"

    @property
    def rope_dim(self) -> int:
        """The values of a query or key head that rotate."""
        return self.qk_rope_head_dim if self.latent else self.head_dim

    @property
    def attn_out_width(self) -> int:
        """What the attention hands to Wo: every head's value."""
        return self.n_heads * (self.v_head_dim if self.latent else self.head_dim)

    @property
    def kv_pool_heads(self) -> int:
        """The page pools' leading axis: ``n_kv_heads``, ``kv_pack`` to a row."""
        return self.n_kv_heads // self.kv_pack

    @property
    def kv_widths(self) -> tuple[int, int]:
        """The last axis of the two cache pools, ``k`` and ``v``. Heads: a
        head's key and value. Latent: ``k`` holds the shared rotated key in a
        whole number of 128-lane rows (64 values and 64 zeros at the published
        width: the padding is the pool's, never a useful byte), ``v`` the
        normed latent, which the scores read too."""
        if not self.latent:
            return (self.head_dim * self.kv_pack,) * 2
        return self.index_key_offset + self.index_head_dim, self.kv_lora_rank

    @property
    def index_key_offset(self) -> int:
        """Where a token's index key starts in its row of the ``k`` pool: past
        the rotated key's lanes. One page id then addresses the latent, the
        rotated key and the index key, and whatever carries a page run (the
        commit, the window write, spill, readmit, snapshot, a radix split)
        carries all three."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def kv_bytes_per_token(self) -> int:
        """USEFUL cache bytes a token's ATTENTION reads over all layers (no
        lane padding; the index key is ``index_bytes_per_token``'s)."""
        import jax.numpy as jnp  # bfloat16 is a dtype to jax's numpy, not to numpy's

        per_layer = (
            self.kv_lora_rank + self.qk_rope_head_dim if self.latent
            else 2 * self.n_kv_heads * self.head_dim
        )
        return per_layer * self.n_attn_layers * jnp.dtype(self.dtype).itemsize

    @property
    def index_bytes_per_token(self) -> int:
        """Bytes of a token's index keys over all layers."""
        import jax.numpy as jnp

        return self.index_head_dim * self.n_layers * jnp.dtype(self.dtype).itemsize

    @property
    def branches_float32(self) -> bool:
        """Whether a branch's output stays float32, as accumulated, until it
        has joined the residual: where it is normed first, and in a latent
        block, whose sharper softmax (``attn_score_factor``) makes it the most
        sensitive to rounding of the blocks here."""
        return self.post_norms or self.latent

    @property
    def kernel_lanes_ok(self) -> bool:
        """Whether Mosaic can tile the paged kernel for this geometry: every
        width it multiplies is a whole number of 128 lanes."""
        return all(w % 128 == 0 for w in self.kv_widths)

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def n_sparse_layers(self) -> int:
        if self.hybrid and not self.conv_ffn:
            return self.layer_pattern.count("E")
        return self.n_layers - self.n_dense_layers if self.n_experts else 0

    @property
    def is_default_block(self) -> bool:
        """True for the block every default describes: what the int8 path,
        the drafter and the trainer were written against."""
        d = GemmaConfig()
        return all(
            getattr(self, f) == getattr(d, f)
            for f in (
                "activation", "tie_embeddings", "scale_embeddings", "norm_plus_one",
                "layer_types", "sliding_window", "yarn_factor", "n_experts",
                "rope_full_layers", "qk_norm", "attn_gate", "post_norms", "attention",
                "layer_pattern",
            )
        )

    def rope_tables(self) -> "tuple[np.ndarray, np.ndarray] | None":
        """Per-layer rope as the layer scan's data: inverse frequencies
        ``[L, head_dim / 2]`` and the factor on cos and sin ``[L]``. None
        where every layer rotates alike with no stretch: the scan then
        carries nothing and the rope is the constant it always was. A full
        layer that does not rotate (``rope_full_layers`` off) gets a row of
        zeros: angle 0 at every position, the identity.

        Sliding layers: ``theta^(-2k/dim)``, factor 1. Full layers under
        YaRN: with ``corr(r) = dim * ln(orig / (2 pi r)) / (2 ln theta)``,
        ``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``
        (clipped to [0, dim - 1]) and ``ramp_k = clip((k - low) / (high -
        low), 0, 1)``, the frequency is ``(1 - ramp_k)`` of the plain one
        plus ``ramp_k`` of the plain one over ``yarn_factor``; factor
        ``yarn_attention_factor``."""
        if not self.yarn_factor and self.rope_full_layers:
            return None
        dim = self.rope_dim
        k = np.arange(dim // 2, dtype=np.float64)
        plain = self.rope_theta ** (-2.0 * k / dim)
        full = np.asarray([t != SLIDING for t in self.layer_types or (FULL,) * self.n_layers])
        if not self.rope_full_layers:
            inv_freq = np.where(full[:, None], 0.0, plain[None, :])
            return inv_freq.astype(np.float32), np.ones(self.n_layers, np.float32)

        def corr(r: float) -> float:
            return dim * math.log(self.yarn_original_max_pos / (2 * math.pi * r)) / (
                2 * math.log(self.rope_theta)
            )

        low = max(math.floor(corr(self.yarn_beta_fast)), 0)
        high = min(math.ceil(corr(self.yarn_beta_slow)), dim - 1)
        ramp = np.clip((k - low) / max(high - low, 1e-3), 0.0, 1.0)
        stretched = (1.0 - ramp) * plain + ramp * plain / self.yarn_factor
        inv_freq = np.where(full[:, None], stretched[None, :], plain[None, :])
        factor = np.where(full, self.yarn_attention_factor, 1.0)
        return inv_freq.astype(np.float32), factor.astype(np.float32)

    def layer_windows(self) -> "np.ndarray | None":
        """Per-layer attention window ``[L]`` as the layer scan's data (a
        full layer's is past any context), or None where no layer has one."""
        if SLIDING not in self.layer_types:
            return None
        return np.asarray(
            [self.sliding_window if t == SLIDING else NO_WINDOW for t in self.layer_types],
            np.int32,
        )

    @property
    def n_params(self) -> int:
        """Parameters HELD (tied embeddings counted once; the experts this
        device holds): what the weights' bytes follow."""
        return self._count(self.n_experts_held)

    @property
    def n_active_params(self) -> int:
        """Parameters a token READS: with a sparse feed-forward only its
        ``n_experts_per_tok`` experts — the basis for model-FLOPs/token ≈
        2 * n_active_params in MFU accounting. Equals ``n_params`` for a
        dense block."""
        return self._count(self.n_experts_per_tok)

    def _count(self, experts: int) -> int:
        D, H, K, hd, F = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        if self.mixer_ffn:
            ffn = 3 * D * F + 2 * D  # the feed-forward and the layer's two norms
            linear = 5 * D * H * hd + 2 * hd + H * hd  # q, k, v, gate, o; q/k norms; the output norm
            block = 3 * D * H * hd + 2 * D * K * hd  # q, gate, o; k, v
            layers = self.n_linear_layers * (linear + ffn) + self.n_block_layers * (block + ffn)
            head = 0 if self.tie_embeddings else D * self.vocab_size
            return self.vocab_size * D + layers + D + head
        if self.conv_ffn:
            conv = 3 * D * D + D * D + D * self.conv_kernel  # w_in, w_out, the taps
            attn = 2 * D * H * hd + 2 * D * K * hd + 2 * hd  # q, o; k, v; the q/k gains
            sparse_ff = (D + bool(self.router_bias_scale)) * self.n_experts + experts * 3 * D * self.d_expert
            n_sparse = self.n_sparse_layers
            layers = (
                self.n_conv_layers * conv + self.n_attn_layers * attn + 2 * D * self.n_layers
                + (self.n_layers - n_sparse) * 3 * D * F + n_sparse * sparse_ff
            )
            head = 0 if self.tie_embeddings else D * self.vocab_size
            return self.vocab_size * D + layers + D + head
        if self.scan_ffn:
            I, N, R = self.scan_inner, self.ssm_state_size, self.mamba_dt_rank
            ffn = 3 * D * F + 2 * D  # the feed-forward and the layer's two norms
            scan = (
                2 * D * I + I * (self.conv_kernel + 1) + I * (R + 2 * N)  # w_in, taps + bias, w_x
                + R + 2 * N + R * I + I  # the three inner gains, w_dt, its bias
                + I * N + I + I * D  # A_log, D_skip, w_out
            )
            attn = 2 * D * H * hd + 2 * D * K * hd  # q, o; k, v
            layers = self.n_scan_layers * (scan + ffn) + self.n_attn_layers * (attn + ffn)
            head = 0 if self.tie_embeddings else D * self.vocab_size
            return self.vocab_size * D + layers + D + head
        if self.hybrid:
            inner, C, Hm = self.mamba_inner, self.conv_width, self.mamba_n_heads
            mamba = (
                D * (inner + C + Hm) + C * (self.conv_kernel + 1)  # w_in, the taps and their bias
                + 3 * Hm + inner + inner * D + D  # dt_bias, A_log, D_skip, the gated norm, w_out, norm
            )
            attn = D * H * hd + 2 * D * K * hd + H * hd * D + D
            Dl = self.moe_latent_size or D
            ff = (
                D + (D + bool(self.router_bias_scale)) * self.n_experts
                + (2 * D * Dl if self.moe_latent_size else 0)
                + experts * (2 if self.activation == "relu2" else 3) * Dl * self.d_expert
                + (2 if self.activation == "relu2" else 3) * D * self.d_shared_expert
            )
            layers = self.n_mamba_layers * mamba + self.n_attn_layers * attn + self.n_sparse_layers * ff
            head = 0 if self.tie_embeddings else D * self.vocab_size
            return self.vocab_size * D + layers + D + head
        attn = D * H * hd + 2 * D * K * hd + H * hd * D + 2 * D
        if self.latent:
            rq, rkv, dr, dv = self.q_lora_rank, self.kv_lora_rank, self.qk_rope_head_dim, self.v_head_dim
            attn = (
                D * rq + rq + rq * H * (hd + dr)  # w_dq, its norm, w_uq
                + D * (rkv + dr) + rkv + rkv * H * (hd + dv)  # w_dkv, its norm, w_ukv
                + H * dv * D + 2 * D  # wo, the layer's two norms
            )
            if self.index_topk:
                Hi, di = self.index_n_heads, self.index_head_dim
                attn += rq * Hi * di + D * di + 2 * di + D * Hi  # w_qi, w_ki, its norm, w_wi
        attn += self.attn_gate * D * H * hd + self.qk_norm * 2 * hd + self.post_norms * 2 * D
        sparse_ff = (
            (D + bool(self.router_bias_scale)) * self.n_experts
            + experts * 3 * D * self.d_expert
            + 3 * D * self.d_shared_expert
        )
        n_sparse = self.n_sparse_layers
        layers = (self.n_layers - n_sparse) * (attn + 3 * D * F) + n_sparse * (attn + sparse_ff)
        head = 0 if self.tie_embeddings else D * self.vocab_size
        return self.vocab_size * D + layers + D + head

    @classmethod
    def named(cls, name: str, *, vocab_size: int = 384, max_seq_len: int = 2048) -> "GemmaConfig":
        presets = {
            # Tiny random-weight config for CPU CI (SURVEY.md §4.5).
            "test": dict(d_model=128, n_layers=2, n_heads=4, n_kv_heads=1, head_dim=32, d_ff=256),
            # Gemma-2B architecture dims (18 layers, MQA).
            "2b": dict(
                d_model=2048, n_layers=18, n_heads=8, n_kv_heads=1, head_dim=256, d_ff=16384
            ),
            # Gemma-7B architecture dims (28 layers, MHA).
            "7b": dict(
                d_model=3072, n_layers=28, n_heads=16, n_kv_heads=16, head_dim=256, d_ff=24576
            ),
        }
        if name not in presets:
            raise ConfigError(f"unknown model size {name!r}; expected one of {sorted(presets)}")
        return cls(vocab_size=vocab_size, max_seq_len=max_seq_len, **presets[name])
