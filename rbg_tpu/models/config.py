"""Model configurations for the llama-family decoder.

One config dataclass covers the model families the reference's examples deploy
(reference: ``examples/inference/*.yaml`` deploy Qwen/Llama/DeepSeek via
SGLang). Presets below mirror the benchmark configs in BASELINE.md.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


class _Numbers(tuple):
    """A tuple of numbers that also equals the list of them: a
    configuration file gives lists, the config is hashed, and a value read
    back from the config equals the one the file gave."""

    def __eq__(self, other):
        return tuple.__eq__(self, tuple(other) if isinstance(other, list)
                            else other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


class _Fields(tuple):
    """A sorted tuple of ``(field, value)`` pairs that also equals the dict
    of them, for ``_Numbers``' reasons."""

    def __eq__(self, other):
        return tuple.__eq__(self, tuple(sorted(other.items()))
                            if isinstance(other, dict) else other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


# What mixes tokens in a layer -> the key its half-layers are stacked under.
MIXER_KEYS = {"full": "mixers", "kda": "kda_mixers", "conv": "conv_mixers",
              "window": "window_mixers"}
# A published ``layer_types`` entry -> the mixer kind.
_LAYER_TYPES = {"full_attention": "full", "conv": "conv",
                "sliding_attention": "window"}
# The mixer kinds that keep K/V pages, each a class of page of its own
# (``engine/kvcache.py``); every other kind keeps a recurrent state.
PAGED_KINDS = ("full", "window")
# What ``attn_gate`` may be: no gate, a gate a channel of every head (True,
# as a file that predates the forms says it), a gate a head.
_GATE_FORMS = (False, True, "channel", "head")


def _half_keys(g) -> tuple:
    """(mixer's params key, MLP's params key) of a layer of kind ``g``."""
    return (MIXER_KEYS[g.attention],
            "moe_mlps" if g.num_experts else "dense_mlps")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Hyperparameters of a llama-family (pre-norm, RoPE, GQA, SwiGLU) decoder."""

    name: str = "tiny"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # Mixture-of-experts (0 = dense). DeepSeek/Mixtral-style sparse MLP with
    # top-k routing + optional always-on shared expert.
    num_experts: int = 0
    experts_per_token: int = 2
    moe_intermediate_size: int = 0      # 0 → intermediate_size
    moe_shared_expert: bool = False
    moe_shared_expert_size: int = 0     # 0 → intermediate_size
    # Leading layers whose MLP is the dense SwiGLU of intermediate_size
    # before the expert layers start (DeepSeek-V3 first_k_dense_replace).
    # Such a model's parameters come in two groups (``layer_groups``).
    first_dense_layers: int = 0
    # The router's rule (``llama._route``): how logits become scores
    # (softmax | sigmoid), a per-expert bias that is added for the top-k
    # selection only and never weighs (noaux_tc), whether the chosen
    # scores are renormalised to sum 1, and the factor they are scaled by.
    moe_scoring: str = "softmax"
    moe_select_bias: bool = False
    moe_renormalize: bool = True
    moe_routed_scale: float = 1.0
    # Multi-head latent attention (DeepSeek-V2/V3): the cache stores ONE
    # compressed latent (kv_lora_rank) + one shared RoPE key
    # (qk_rope_head_dim) per token instead of per-head K/V — an order of
    # magnitude less KV HBM, which is what makes long-context PD
    # disaggregation cheap to ship around. num_kv_heads is ignored.
    mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Low-rank query (DeepSeek-V3): wq_a → RMSNorm → wq_b in place of wq
    # (0 = one full-rank wq).
    q_lora_rank: int = 0
    # RoPE pairing: rotate-half pairs (x_i, x_{i+hd/2}); interleaved pairs
    # (x_2i, x_2i+1), the published DeepSeek convention.
    rope_interleave: bool = False
    # False: attention without positions: neither path rotates its queries
    # and keys (``llama._qkv``, ``_mla_qkv``). The latent attention's
    # ``qk_rope_head_dim`` channels stay, as plain shared key channels
    # (Kimi-Linear ``mla_use_nope``); grouped-query attention caches its
    # keys as projected (Solar-Open2 ``use_rope`` false).
    use_rope: bool = True
    # An output gate on grouped-query attention (Solar-Open2
    # ``use_gqa_gate``; arXiv:2505.06708's elementwise form): ``wo (o *
    # sigmoid(x~ wg))``, ``wg [d, heads x head_dim]``, a gate a channel of
    # every head, from the layer's normed input (``llama._attn_gate``).
    # The FORM is the value: ``"channel"`` (what ``True`` means) or
    # ``"head"``, one scalar a head a token, ``wg [d, heads]`` (Laguna
    # ``gating``; the paper's headwise form); ``gate_a_head`` reads it.
    attn_gate: object = False
    # Rotary settings (``ops/rope.py::rotary_tables``). The first
    # ``partial_rotary_factor`` of a head's channels are rotated and the
    # rest pass as they are; ``rope_scaling`` ``"yarn"`` blends each
    # frequency between its own and its ``1 / rope_factor`` by where its
    # wavelength lies against ``rope_original_max`` positions
    # (``rope_beta_fast`` / ``rope_beta_slow`` rotations) and multiplies
    # cos and sin by ``rope_attention_factor`` (0: ``0.1 ln(factor) + 1``).
    # Of this config's attention; a window layer's are ``window_layer``'s.
    partial_rotary_factor: float = 1.0
    rope_scaling: str = ""
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 0.0
    # Window layers (``layer_types`` ``sliding_attention``, mixer kind
    # ``window``): a query attends the ``sliding_window`` newest keys, its
    # own included (``j > i - sliding_window``), so a row's pages of such a
    # layer are given back once they lie below every window a later query
    # can have: a second class of page (``engine/kvcache.py``).
    # ``window_layer`` holds what else differs in a window layer, ``{field:
    # value}`` over this config's (its head count, its rotary settings); the
    # KIND's group config (``layer_groups``) is this config with them.
    sliding_window: int = 0
    window_layer: object = ()
    # Recurrent layers (Kimi Delta Attention, ``ops/kda.py``): the 1-based
    # numbers, as published, of the layers that mix tokens by a gated delta
    # rule over a fixed state ``[kda_num_heads, kda_head_dim, kda_head_dim]``
    # a sequence in place of this config's attention. q, k and v pass a
    # causal depthwise convolution of ``kda_conv_kernel`` taps; the decay
    # and the output gate are low-rank (``kda_rank``). Such a model's
    # layers come in runs by kind (``layer_groups``) and its cache is pages
    # for the attention layers and a state slot a row for these
    # (``engine/kvcache.py::StatePool``).
    kda_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4
    kda_rank: int = 128
    # The write strength is ``kda_beta_scale x sigmoid(.)``: 1 keeps ``I -
    # b k k^T``'s eigenvalues in [0, 1]; 2 lets them reach -1 (Solar-Open2
    # ``kda_allow_neg_eigval``).
    kda_beta_scale: float = 1.0
    # What mixes tokens in each layer, as published (LFM2 ``layer_types``):
    # ``full_attention`` (this config's attention) or ``conv``, the gated
    # short convolution (``ops/short_conv.py``): ``[B, C, X] = W_in x``,
    # a causal depthwise convolution of ``conv_kernel`` taps over ``B * X``
    # with no activation, ``W_out (C * .)``. Its state is the last
    # ``conv_kernel - 1`` values of ``B * X`` a sequence, kept in a
    # ``StatePool`` slot like a KDA layer's. Empty: ``kda_layers`` says
    # which layers are recurrent (``mixer_kinds`` reads both).
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 3
    # RMSNorm over each query and key head, with a learned weight, before
    # RoPE (GQA attention only).
    qk_norm: bool = False
    # A looped stack (Ouro ``total_ut_steps``; arXiv:2510.25741): the layers
    # run ``loop_steps`` times a token over the SAME weights, the model's
    # final norm closing every pass (its output enters the next), and a
    # token's keys and values differ by pass, so pass ``t`` of layer ``l``
    # keeps a cache entry of its own, ``t * num_layers + l``
    # (``cache_layers``; ``llama.paged_layers``). ``post_norms``: a second
    # norm a sub-layer, on the mixer's and on the MLP's OUTPUT before the
    # residual add (sandwich norms). ``exit_gate``: the parameters hold the
    # loop's exit gate (``exit_gate: {w [d], b [1]}``), one scalar a token
    # a pass; at ``early_exit_threshold`` 1, the only value served, no
    # token leaves before the last pass and the gate is held, not computed:
    # the two leaves are there for ``num_params`` (2049 of the published
    # count) and for the checkpoint loader that is to fill them.
    # ``early_exit_threshold`` is a field only to be refused by name: a
    # configuration file's ``preset`` reaches the program as
    # ``ModelConfig(**preset)`` and nowhere else.
    loop_steps: int = 1
    post_norms: bool = False
    exit_gate: bool = False
    early_exit_threshold: float = 1.0
    # Grouped-query attention's input projections held ``[L, out, in]``
    # (``wq [L, h hd, d]``, ``wk``, ``wv`` likewise), the layout their dots
    # take on the chip: held ``[L, in, out]`` the compiler transposes a
    # layer's slice in every trip, or the whole stack in every step where
    # a kind's loop has several trips (ROADMAP S23 + S14). No option: a
    # model with window layers holds them so, in both its kinds (it takes
    # no adapters, ``unbuilt_for``), and so does a looped model, whose
    # layer scan stands inside the passes' (held ``[L, in, out]`` its three
    # stacks were transposed whole in every step, 1.2 GB of temporaries at
    # 48 layers of 2048; PERF.md, PR 50); no constructor takes the field:
    # ``__post_init__`` reads it off ``sliding_window`` and ``loop_steps``
    # and ``_kind`` hands it to a group config.
    proj_out_in: bool = dataclasses.field(default=False, init=False)
    # What a GROUP config's layers mix tokens by: ``full`` (this config's
    # attention), ``window`` (the same under ``window_layer``'s fields,
    # within ``sliding_window``), ``kda`` or ``conv``. Set by
    # ``layer_groups`` alone.
    attention: str = "full"
    # Which half of a layer a group config of ``param_groups`` stands for:
    # ``mixer`` (norm, what mixes tokens, its output projection), ``mlp``
    # (norm, MLP or experts), or both (""). Set by ``param_groups`` alone.
    half: str = ""
    # The contiguous range ``[lo, hi)`` of routed experts this device holds
    # (None: all). The router keeps its published width; the expert stacks
    # hold ``hi - lo`` experts and only their part of the layer is
    # computed: what the absent experts would add is added elsewhere (a
    # deployment that divides a layer's experts over chips).
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        object.__setattr__(self, "kda_layers", _Numbers(self.kda_layers))
        object.__setattr__(self, "layer_types", _Numbers(self.layer_types))
        if self.layer_types:
            unknown = sorted(set(self.layer_types) - set(_LAYER_TYPES))
            if (unknown or self.kda_layers
                    or len(self.layer_types) != self.num_layers):
                raise ValueError(
                    f"layer_types names a kind for each of the "
                    f"{self.num_layers} layers, one of {sorted(_LAYER_TYPES)}"
                    f", and leaves kda_layers empty; got "
                    f"{len(self.layer_types)} entries, unknown {unknown}, "
                    f"kda_layers {tuple(self.kda_layers)}")
        if self.attn_gate not in _GATE_FORMS:
            raise ValueError(f"attn_gate is one of {_GATE_FORMS} (True: "
                             f"'channel'); got {self.attn_gate!r}")
        if self.rope_scaling not in ("", "yarn"):
            raise ValueError(
                f"rope_scaling {self.rope_scaling!r}: only 'yarn' is built "
                f"(ops/rope.py::rotary_tables)")
        window_layer = dict(self.window_layer)
        unknown = sorted(set(window_layer) - {
            f.name for f in dataclasses.fields(self)})
        if unknown:
            raise ValueError(f"window_layer names {unknown}: no field of "
                             f"ModelConfig")
        object.__setattr__(self, "window_layer",
                           _Fields(sorted(window_layer.items())))
        if ("window" in self.mixer_kinds or self.attention == "window") \
                != bool(self.sliding_window):
            raise ValueError(
                f"sliding_window {self.sliding_window} and layer_types "
                f"disagree: a sliding_attention layer needs a window, and a "
                f"window needs such a layer")
        if self.sliding_window and self.mla:
            raise ValueError("window layers are grouped-query attention's; "
                             "the latent attention (mla) has none")
        if window_layer.get("sliding_window",
                            self.sliding_window) != self.sliding_window:
            raise ValueError(
                f"window_layer.sliding_window "
                f"{window_layer['sliding_window']} is not sliding_window "
                f"{self.sliding_window}: the model's mask and the engine's "
                f"pages read one window")
        object.__setattr__(self, "proj_out_in", bool(self.sliding_window)
                           or self.loop_steps > 1)
        if self.attn_gate and self.mla:
            raise ValueError(
                "attn_gate gates grouped-query attention's output; the "
                "latent attention (mla) has no gate")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: the layers run "
                             f"at least once")
        if self.early_exit_threshold != 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold}: only 1 "
                f"is served (every token runs every pass); a depth that "
                f"differs by row is a scheduler the engine lacks")
        if self.loop_steps > 1 and (self.by_kind or self.mla
                                    or self.num_experts):
            raise ValueError(
                f"loop_steps {self.loop_steps}: a looped stack is built for "
                f"layers of one kind on grouped-query attention with a dense "
                f"MLP (the walk by kind, the latent attention and the "
                f"experts' counters run a layer once)")
        if self.experts_held is not None:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.num_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} is no range of the "
                    f"{self.num_experts} experts")
            object.__setattr__(self, "experts_held", _Numbers((lo, hi)))

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def moe_f(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def moe_shared_f(self) -> int:
        return self.moe_shared_expert_size or self.intermediate_size

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this device holds."""
        if self.experts_held is None:
            return self.num_experts
        return self.experts_held[1] - self.experts_held[0]

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        """What mixes tokens in each layer, in order: ``full`` or ``window``
        (pages, a class each), ``kda`` or ``conv`` (a slot of the state
        pool)."""
        if self.layer_types:
            return tuple(_LAYER_TYPES[t] for t in self.layer_types)
        return tuple("kda" if layer + 1 in self.kda_layers else "full"
                     for layer in range(self.num_layers))

    def mixer_count(self, kind: str) -> int:
        """Layers whose mixer is ``kind``."""
        return self.mixer_kinds.count(kind)

    @property
    def cache_layers(self) -> int:
        """Entries on the leading axis of the full class's page pool (and
        of the contiguous ``KVCache``): one a (pass, attention layer), pass
        ``t`` of the ``l``-th attention layer at ``t * mixer_count("full")
        + l``. A model that runs its layers once: its attention layers."""
        return self.loop_steps * self.mixer_count("full")

    @property
    def looped_for(self) -> str:
        """What a looped model has, as the start of a refusal's message;
        empty where the layers run once. NOT ``unbuilt_for``: such a model
        is served like any one-kind model, prefix reuse included, and only
        what walks layers outside ``llama.paged_layers`` refuses it."""
        if self.loop_steps == 1:
            return ""
        return (f"runs its layers {self.loop_steps} times a token (a cache "
                f"entry a pass a layer, {self.cache_layers})")

    @property
    def recurrent_kinds(self) -> Tuple[str, ...]:
        """The mixer kinds that keep a recurrent state in place of pages,
        by name (a refusal's message names them)."""
        return tuple(sorted(set(self.mixer_kinds) - set(PAGED_KINDS)))

    @property
    def recurrent(self) -> bool:
        """Whether some layer keeps a recurrent state in place of pages
        (the engine holds a ``StatePool``)."""
        return bool(self.recurrent_kinds)

    @property
    def by_kind(self) -> bool:
        """Whether the layers are stacked and walked by KIND: some layer's
        mixer is not this config's own attention, so the parameters come in
        half-layer stacks (``param_groups``) and the pools are walked by
        ``llama._hybrid_layers``. Says nothing of what the kinds keep:
        ``recurrent`` (a state a row) and ``sliding_window`` (a second
        class of page) say that."""
        return set(self.mixer_kinds) != {"full"}

    @property
    def unbuilt_for(self) -> str:
        """Why this model is served by the unified engine over its pools
        alone, as the start of a refusal's message; empty where nothing is
        refused. What is refused and why is the refuser's to say."""
        if self.recurrent:
            return (f"has recurrent layers "
                    f"({', '.join(self.recurrent_kinds)})")
        if self.sliding_window:
            return (f"has window layers (sliding_window "
                    f"{self.sliding_window})")
        return ""

    def _kind(self, **fields):
        """This config with ``fields`` replaced, as a KIND's group config:
        it holds its projections as this one does (``proj_out_in``)."""
        kind = dataclasses.replace(self, **fields)
        object.__setattr__(kind, "proj_out_in", self.proj_out_in)
        return kind

    @property
    def layer_groups(self):
        """``((key, group config, lo, hi), ...)``: the runs of layers of one
        kind, in order; ``lo``/``hi`` are absolute layer numbers. A kind is
        (what mixes tokens, what the MLP is): ``blocks`` (this config's
        attention, experts if it has any), ``dense_blocks`` (the same
        before the expert layers start), ``kda_blocks`` and
        ``kda_dense_blocks`` (the recurrent mixer). A model of one kind of
        layer is the one group ``blocks`` and the group config is this
        config itself. Where every kind stands in ONE run (no recurrent
        layers) a run's key is its parameters' too, stacked with a
        leading axis ``hi - lo``; a model whose kinds alternate names a
        key in several runs and stacks its parameters by half-layer
        (``param_groups``)."""
        n = self.num_layers - self.num_moe_layers if self.num_experts else 0
        if not n and not self.by_kind:
            return (("blocks", self, 0, self.num_layers),)
        if not self.by_kind:
            dense = dataclasses.replace(self, num_experts=0,
                                        first_dense_layers=0)
            return (("dense_blocks", dense, 0, n),
                    ("blocks", self, n, self.num_layers))
        kinds, runs = {}, []
        for layer, mixer in enumerate(self.mixer_kinds):
            dense = layer < n
            key = ("" if mixer == "full" else mixer + "_") \
                + ("dense_" if dense else "") + "blocks"
            if key not in kinds:
                kinds[key] = self._kind(
                    kda_layers=(), layer_types=(),
                    first_dense_layers=0, attention=mixer,
                    **({"num_experts": 0, "experts_held": None}
                       if dense else {}),
                    **(dict(self.window_layer, window_layer=())
                       if mixer == "window" else
                       {"sliding_window": 0, "window_layer": ()}))
            if runs and runs[-1][0] == key:
                runs[-1][3] = layer + 1
            else:
                runs.append([key, kinds[key], layer, layer + 1])
        return tuple(tuple(r) for r in runs)

    @property
    def param_groups(self):
        """``((params key, group config, layers), ...)``: what is stacked
        under each key of the parameters, with a leading axis ``layers``.
        The groups of ``layer_groups`` where each stands in one run. A
        model with recurrent layers stacks HALF-layers, in layer order:
        the mixers by kind (``kda_mixers``, ``conv_mixers``, ``mixers``)
        and the MLPs by
        kind (``dense_mlps``, ``moe_mlps``), so that a walk compiles each
        mixer once whatever MLP follows it (``layer_halves`` says which
        entries are a layer's)."""
        if not self.by_kind:
            return tuple((key, g, hi - lo) for key, g, lo, hi
                         in self.layer_groups)
        groups = {}
        for key, g, lo, hi in self.layer_groups:
            for name, half in zip(_half_keys(g), ("mixer", "mlp")):
                if name not in groups:
                    groups[name] = [name, g._kind(half=half), 0]
                groups[name][2] += hi - lo
        return tuple(tuple(v) for v in groups.values())

    @property
    def layer_halves(self):
        """Per layer of a model with recurrent layers: ``(group config,
        mixer's params key, its ordinal there, MLP's params key, its
        ordinal there)``; the group config is the layer's kind's
        (``layer_groups``)."""
        seen, out = {}, []
        for _, g, lo, hi in self.layer_groups:
            for _ in range(lo, hi):
                mixer, mlp = _half_keys(g)
                out.append((g, mixer, seen.get(mixer, 0), mlp,
                            seen.get(mlp, 0)))
                seen[mixer] = seen.get(mixer, 0) + 1
                seen[mlp] = seen.get(mlp, 0) + 1
        return tuple(out)

    @property
    def num_moe_layers(self) -> int:
        """Layers with routed experts: all but the leading dense ones."""
        if not self.num_experts:
            return 0
        return self.num_layers - min(self.first_dense_layers, self.num_layers)

    def _gqa_params(self, heads: int) -> int:
        """Parameters of one grouped-query mixer of ``heads`` query heads:
        q, k, v, o, the head norms, the gate by its form."""
        d, hd = self.hidden_size, self.head_dim_
        n = 2 * d * heads * hd + 2 * d * self.num_kv_heads * hd
        if self.qk_norm:
            n += 2 * hd
        if self.attn_gate:      # wg: a channel of every head, or a head
            n += d * heads * (1 if self.gate_a_head else hd)
        return n

    @property
    def gate_a_head(self) -> bool:
        """Whether ``attn_gate`` is the headwise form (else, where there
        is a gate, a gate a channel)."""
        return self.attn_gate == "head"

    @property
    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.head_dim_
        if self.mla:
            h, dc = self.num_heads, self.kv_lora_rank
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            rq = self.q_lora_rank
            wq = (d * rq + rq + rq * h * (dn + dr)) if rq else d * h * (dn + dr)
            attn = (wq                       # wq, or wq_a + q_norm + wq_b
                    + d * (dc + dr) + dc     # w_dkv + kv_norm
                    + dc * h * dn            # w_uk
                    + dc * h * dv            # w_uv
                    + h * dv * d)            # wo
        else:
            attn = self._gqa_params(self.num_heads)
        dense_mlp = 3 * d * f
        # The experts this device holds; the router is whole.
        moe_mlp = self.experts_here * 3 * d * self.moe_f + d * self.num_experts
        if self.moe_select_bias:
            moe_mlp += self.num_experts
        if self.moe_shared_expert:
            moe_mlp += 3 * d * self.moe_shared_f
        n_moe = self.num_moe_layers
        mlp = n_moe * moe_mlp + (self.num_layers - n_moe) * dense_mlp
        head = 0 if self.tie_word_embeddings else d * v
        kh, kd, r = self.kda_num_heads, self.kda_head_dim, self.kda_rank
        ch = kh * kd
        kda = (3 * d * ch + self.kda_conv_kernel * 3 * ch   # qkv, conv
               + 2 * (d * r + r * ch)       # decay and gate, low-rank
               + kh + ch + d * kh           # A_log, dt_bias, beta
               + kd + ch * d)               # o_norm, wo
        conv = 3 * d * d + self.conv_kernel * d + d * d     # in, taps, out
        window = self._gqa_params(
            dict(self.window_layer).get("num_heads", self.num_heads))
        attn_all = sum({"full": attn, "kda": kda, "conv": conv,
                        "window": window}[kind] for kind in self.mixer_kinds)
        norms = 4 if self.post_norms else 2      # a layer's norm weights
        gate = d + 1 if self.exit_gate else 0
        return (v * d + attn_all + self.num_layers * norms * d + mlp + d
                + head + gate)


_PRESETS = {
    # Tiny config for tests — compiles in seconds on CPU.
    "tiny": ModelConfig(
        name="tiny", vocab_size=256, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        rope_theta=10000.0, dtype="float32",
    ),
    # Small config for single-chip benching — fits v5e-1 HBM easily.
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", vocab_size=151936, hidden_size=896,
        intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
        head_dim=64, max_seq_len=32768, rope_theta=1000000.0,
        tie_word_embeddings=True,
    ),
    "llama3-1b": ModelConfig(
        name="llama3-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0, tie_word_embeddings=True,
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0,
    ),
    # MoE family (DeepSeek/Mixtral-style) — the reference's config 5 deploys
    # DeepSeek-V3 multi-host (BASELINE.md).
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=256, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        rope_theta=10000.0, dtype="float32",
        num_experts=4, experts_per_token=2, moe_intermediate_size=96,
        moe_shared_expert=True,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=32768, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2,
    ),
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite", vocab_size=102400, hidden_size=2048,
        intermediate_size=10944, num_layers=27, num_heads=16, num_kv_heads=16,
        max_seq_len=163840, rope_theta=10000.0,
        num_experts=64, experts_per_token=6, moe_intermediate_size=1408,
        moe_shared_expert=True, moe_shared_expert_size=2816,
        mla=True, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        # From memory of the published config.json (assumed): one dense
        # layer first, full-rank query, softmax scores that are not
        # renormalised, interleaved rotary pairs.
        first_dense_layers=1, moe_renormalize=False, rope_interleave=True,
    ),
    "deepseek-v3": ModelConfig(
        name="deepseek-v3", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128,
        num_kv_heads=128, max_seq_len=163840, rope_theta=10000.0,
        num_experts=256, experts_per_token=8, moe_intermediate_size=2048,
        moe_shared_expert=True, moe_shared_expert_size=2048,
        mla=True, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        # From memory of the published config.json (assumed): three dense
        # layers first, low-rank query, sigmoid scores with the noaux_tc
        # selection bias, renormalised and scaled by 2.5. Its group-limited
        # selection (n_group 8, topk_group 4) is not modeled.
        first_dense_layers=3, q_lora_rank=1536, rope_interleave=True,
        moe_scoring="sigmoid", moe_select_bias=True, moe_routed_scale=2.5,
    ),
    # Tiny MLA config for tests — compiles in seconds on CPU.
    "tiny-mla": ModelConfig(
        name="tiny-mla", vocab_size=256, hidden_size=128,
        intermediate_size=384, num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_len=256, rope_theta=10000.0, dtype="float32",
        mla=True, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32,
    ),
    # Tiny DeepSeek-V3-shaped block for tests (the layer of the benchmark's
    # joyai-llm-flash): a dense layer before expert layers, latent attention
    # with a low-rank query and interleaved rotary pairs, sigmoid routing
    # with a selection bias and a scaling factor, a shared expert.
    "tiny-joyai": ModelConfig(
        name="tiny-joyai", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=3, num_heads=4, num_kv_heads=4,
        max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-6,
        dtype="float32",
        num_experts=16, experts_per_token=4, moe_intermediate_size=48,
        moe_shared_expert=True, moe_shared_expert_size=48,
        first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.5,
        mla=True, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, q_lora_rank=96, rope_interleave=True,
    ),
    # Tiny Kimi-Linear-shaped model for tests (the layers of the benchmark's
    # kimi-linear-48b-a3b): a dense recurrent layer, then expert layers
    # K K M K K K M, the latent attention without positions and with a
    # full-rank query, a held range of the experts.
    "tiny-kimi-linear": ModelConfig(
        name="tiny-kimi-linear", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=8, num_heads=4, num_kv_heads=4,
        max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-5,
        dtype="float32",
        num_experts=16, experts_per_token=2, moe_intermediate_size=48,
        moe_shared_expert=True, moe_shared_expert_size=48,
        first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.446, experts_held=(4, 12),
        mla=True, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, use_rope=False,
        kda_layers=(1, 2, 3, 5, 6, 7), kda_num_heads=4, kda_head_dim=32,
        kda_rank=16,
    ),
    # Tiny LFM2-shaped model for tests (the layers of the benchmark's
    # lfm2-24b-a2b): two dense layers with the gated short convolution,
    # then expert layers A C C C A C C C (A: grouped-query attention with
    # head norms over heads of 64, whose pages pack two heads a lane tile;
    # C: the convolution), bias-selected sigmoid experts without a shared
    # one, a held range of them, a tied head.
    "tiny-lfm2": ModelConfig(
        name="tiny-lfm2", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=10, num_heads=4, num_kv_heads=2,
        head_dim=64, max_seq_len=256, rope_theta=1000000.0,
        rms_norm_eps=1e-5, dtype="float32", tie_word_embeddings=True,
        num_experts=16, experts_per_token=4, moe_intermediate_size=48,
        first_dense_layers=2, moe_scoring="sigmoid", moe_select_bias=True,
        experts_held=(4, 12), qk_norm=True, conv_kernel=3,
        layer_types=("conv", "conv") + ("full_attention", "conv", "conv",
                                        "conv") * 2,
    ),
    # Tiny Solar-Open2-shaped model for tests (the layers of the benchmark's
    # solar-open2-250b): expert layers A K K K A K K K with no dense layer
    # (A: grouped-query attention without positions and with an output
    # gate, on K/V pages; K: the gated delta rule with a write strength of
    # 2 sigmoid, its states beside those pages), sigmoid experts picked by
    # a bias beside a shared one, a held range of them.
    "tiny-solar-open2": ModelConfig(
        name="tiny-solar-open2", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=8, num_heads=4, num_kv_heads=2,
        head_dim=32, max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-5,
        dtype="float32",
        num_experts=16, experts_per_token=4, moe_intermediate_size=48,
        moe_shared_expert=True, moe_shared_expert_size=48,
        moe_scoring="sigmoid", moe_select_bias=True, experts_held=(4, 8),
        use_rope=False, attn_gate=True,
        kda_layers=(2, 3, 4, 6, 7, 8), kda_num_heads=4, kda_head_dim=32,
        kda_rank=16, kda_beta_scale=2.0,
    ),
    # Tiny Laguna-shaped model for tests (the layers of the benchmark's
    # laguna-xs2): two periods F W W W (F: full attention of 6 heads that
    # rotates half its channels under YaRN; W: 8 heads within a window of 8
    # tokens, plain RoPE over the whole head, its pages a class of their
    # own), 2 KV heads in both (groups of 3 and 4), a gate a head, a dense
    # first layer, bias-selected sigmoid experts scaled by 2.5 beside a
    # shared one, a held range of them.
    "tiny-laguna": ModelConfig(
        name="tiny-laguna", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=8, num_heads=6, num_kv_heads=2,
        head_dim=32, max_seq_len=256, rope_theta=50000.0, rms_norm_eps=1e-6,
        dtype="float32",
        num_experts=16, experts_per_token=4, moe_intermediate_size=48,
        moe_shared_expert=True, moe_shared_expert_size=48,
        first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.5, experts_held=(4, 12),
        attn_gate="head",
        partial_rotary_factor=0.5, rope_scaling="yarn",
        rope_factor=8.0, rope_original_max=32, rope_beta_fast=4.0,
        rope_beta_slow=1.0, rope_attention_factor=1.2079441541679836,
        sliding_window=8,
        window_layer={"num_heads": 8, "rope_theta": 10000.0,
                      "partial_rotary_factor": 1.0, "rope_scaling": ""},
        layer_types=("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",) + ("sliding_attention",) * 3,
    ),
    # Tiny Ouro-shaped model for tests (the layers of the benchmark's
    # ouro-2.6b): 2 layers run 3 times a token, a cache entry a pass a
    # layer (6), plain multi-head attention (4 x 4: a group of ONE query
    # head), sandwich norms, the exit gate held.
    "tiny-ouro": ModelConfig(
        name="tiny-ouro", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=32, max_seq_len=256, rope_theta=1000000.0,
        rms_norm_eps=1e-6, dtype="float32",
        loop_steps=3, post_norms=True, exit_gate=True,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(_PRESETS)}")
    cfg = _PRESETS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def list_presets():
    return sorted(_PRESETS)
