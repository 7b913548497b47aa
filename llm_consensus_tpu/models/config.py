"""Model family configurations.

One generic decoder-only transformer (models/transformer.py) covers every
family the framework serves — Llama-2/3, Mistral, Gemma, Qwen2, Mixtral,
DeepSeek-V2, Falcon-H1, Nemotron-H, Solar-Open2, AFMoE — via static config switches, so each (family, shape) pair
compiles to a single XLA program. Every field a family adds defaults to
"off", so the older presets hash and compare as they did. The reference framework's "model set" is a table of
remote API names (/root/reference/cmd/llm-consensus/main.go:49-61); here the
catalog describes real on-device architectures.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # llama | mistral | gemma | qwen2 | mixtral | deepseek_v2 | falcon_h1 | nemotron_h | solar_open2 | afmoe
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float = 10000.0
    # Llama-3.1 NTK scaling: (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); tuple so the config stays hashable.
    rope_scaling: Optional[tuple[float, float, float, int]] = None
    rms_eps: float = 1e-5
    activation: str = "silu"        # silu | gelu_tanh | relu2
    norm_offset: float = 0.0        # gemma: weights parameterized as (1 + w)
    embed_scale: bool = False       # gemma: embeddings scaled by sqrt(d_model)
    qkv_bias: bool = False          # qwen2
    sliding_window: Optional[int] = None  # mistral
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    tie_embeddings: bool = False
    n_experts: int = 0              # routed experts HELD here (mixtral: 8)
    experts_per_token: int = 0      # mixtral: 2
    max_seq_len: int = 8192
    # -- routed experts beyond Mixtral's (ops/moe.py) ----------------------
    # The router keeps its published width; a chip that holds a share of an
    # expert-parallel layer states which experts are its own:
    # [first_expert, first_expert + n_experts) of router_width.
    router_width: int = 0           # router outputs; 0 = n_experts (all held)
    first_expert: int = 0           # index of the first expert held here
    d_expert: int = 0               # one routed expert's width; 0 = d_ff
    n_shared_experts: int = 0       # always-on experts, d_expert wide each
    n_expert_groups: int = 1        # device-limited routing: groups ...
    groups_per_token: int = 1       # ... and how many of them a token may reach
    routed_scale: float = 1.0       # multiplies the routed sum
    norm_topk: bool = True          # chosen weights renormalised to sum to 1
    router_scoring: str = "softmax"  # over the router's whole width; or
                                     # "sigmoid_bias": sigmoid scores, chosen
                                     # by score + a stored per-expert bias
    n_dense_layers: int = 0         # leading layers with a dense MLP of d_ff
    moe_latent: int = 0             # routed experts work in this width,
                                    # between two projections; 0 = d_model
    gated_experts: bool = True      # False: W2 act(W1 x), routed and shared
    d_shared: int = 0               # the shared expert's width; 0 =
                                    # n_shared_experts * expert_width
    # -- latent attention (MLA, ops/latent_attention.py); 0 = off ----------
    q_lora_rank: int = 0
    kv_lora_rank: int = 0           # the cache holds kv_lora_rank + qk_rope_dim a token a layer
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN: (factor, beta_fast, beta_slow, mscale, mscale_all_dim,
    # original_max_position_embeddings); tuple so the config stays hashable.
    rope_yarn: Optional[tuple[float, float, float, float, float, int]] = None
    # -- state-space mixer beside attention in every layer (Mamba-2,
    # ops/ssm.py); ssm_heads 0 = off. The cache then holds, beside keys and
    # values, a recurrent state and a convolution tail a ROW a layer.
    ssm_heads: int = 0              # mixer heads
    ssm_head_dim: int = 0           # inner width = ssm_heads * ssm_head_dim
    ssm_state: int = 0              # state size a head channel
    ssm_groups: int = 1             # groups that share one B and one C
    ssm_conv: int = 4               # causal convolution length
    ssm_chunk: int = 128            # positions a chunk of the chunked scan
    # -- fixed multipliers (muP, Falcon-H1); 1.0 = off --------------------
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # over the in-projection's segments z | x | B | C | dt
    ssm_multipliers: tuple[float, float, float, float, float] = (1.0,) * 5
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)  # gate, down
    # -- a stack whose every layer is ONE part (Nemotron-H): one character
    # a layer, "M" a state-space mixer, "E" the routed expert layer, "*"
    # attention, "K" a delta-rule layer; "" = the uniform layer (attention
    # [+ mixer], then an MLP). Each kind has a parameter stack and a cache of
    # its own length. A published two-part layer ``x += mixer(norm(x)); x +=
    # moe(norm(x))`` is two one-part layers: Solar-Open2's period of four is
    # "*EKEKEKE". "W" is attention under a window of its own beside "*"
    # (AFMoE: ``sliding_window`` is then the window of the "W" layers alone
    # and "*" sees every position; both share one stack and one cache, a
    # layer's place among the attention layers its index), "D" a dense gated
    # MLP of ``d_ff`` (a leading dense layer's second part).
    layer_kinds: str = ""
    rotary: bool = True             # False: attention applies no rotary embedding
    qk_norm: bool = False           # queries and keys RMS-normed over the head's
                                    # width (one weight a projection) before rotary
    post_norm: bool = False         # a one-part layer ends in a norm of its own:
                                    # x + norm(part(norm(x)))
    attn_out_gate: bool = False     # attention's output times sigmoid(h W_gate)
                                    # elementwise, before wo
    # -- delta-rule layer (Kimi Delta Attention, ops/delta.py), kind "K";
    # kda_heads 0 = off. The cache then holds a matrix state
    # [kda_head_dim, kda_head_dim] a head and one convolution tail over
    # q | k | v a ROW a layer.
    kda_heads: int = 0
    kda_head_dim: int = 0           # keys and values alike
    kda_conv: int = 4               # causal convolution length
    kda_chunk: int = 64             # positions a chunk of the chunked rule
    kda_rank: int = 0               # width of the two low-rank gates
    kda_neg_eigval: bool = False    # beta in (0, 2): eigenvalues in [-1, 1]

    def __post_init__(self):
        if self.layer_kinds:
            odd = set(self.layer_kinds) - set("ME*KWD")
            if odd or len(self.layer_kinds) != self.n_layers:
                raise ValueError(
                    f"{self.name}: layer_kinds {self.layer_kinds!r} needs one of "
                    f"'M', 'E', '*', 'K', 'W', 'D' for each of n_layers = "
                    f"{self.n_layers}")
            for kind, sized, what in (("M", self.has_ssm, "ssm_heads"),
                                      ("K", self.has_kda, "kda_heads")):
                if (kind in self.layer_kinds) != sized:
                    raise ValueError(
                        f"{self.name}: layer_kinds {self.layer_kinds!r} and "
                        f"{what} disagree on whether there is a {kind!r} layer")
            if self.has_ssm and self.has_kda:
                raise ValueError(
                    f"{self.name}: one cache holds one kind of state: 'M' "
                    "and 'K' layers in one stack are not computed")
            if "W" in self.layer_kinds and not self.sliding_window:
                raise ValueError(
                    f"{self.name}: a 'W' layer attends under sliding_window, "
                    "which is not set")
            if "D" in self.layer_kinds and self.d_ff <= 0:
                raise ValueError(
                    f"{self.name}: a 'D' layer is a dense MLP of d_ff, which is "
                    f"{self.d_ff}")
            if self.has_state and (
                    self.post_norm or set("WD") & set(self.layer_kinds)):
                raise ValueError(
                    f"{self.name}: 'W' and 'D' layers and a post-norm beside a "
                    "layer that keeps a state ('M', 'K') are not computed")
        elif self.has_kda:
            raise ValueError(
                f"{self.name}: a delta-rule layer is a one-part layer: "
                "kda_heads needs layer_kinds with 'K'")
        elif self.post_norm:
            raise ValueError(
                f"{self.name}: a post-norm ends a one-part layer: post_norm "
                "needs layer_kinds")
        if self.qk_norm and self.is_latent:
            raise ValueError(
                f"{self.name}: a latent (MLA) model norms its low-rank "
                "queries and latents: qk_norm over heads is not computed")

    def kind_layers(self, kind: str) -> tuple[int, ...]:
        """The indices, in the whole stack, of the layers of ``kind``."""
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold keys and values in the cache."""
        if not self.layer_kinds:
            return self.n_layers
        return self.layer_kinds.count("*") + self.layer_kinds.count("W")

    @property
    def attn_kinds(self) -> tuple[tuple[str, Optional[int], bool], ...]:
        """Each attention kind the stack has as ``(kind, window, rotary)``:
        what ``forward`` makes a mask and a decode sweep plan for, once a
        kind. Without a "W" layer the one kind is "*" under
        ``sliding_window``, as every uniform model's layer is; a "W" layer
        always applies the rotary embedding (``rotary`` is what "*" does)."""
        if "W" not in self.layer_kinds:
            return (("*", self.sliding_window, self.rotary),)
        kinds = (("*", None, self.rotary),
                 ("W", self.sliding_window, True))
        return tuple(k for k in kinds if k[0] in self.layer_kinds)

    @property
    def n_window_layers(self) -> int:
        """Attention layers under ``sliding_window``: the "W" layers, or
        without one every attention layer of a model that states a window."""
        if "W" in self.layer_kinds:
            return self.layer_kinds.count("W")
        return self.n_attn_layers if self.sliding_window else 0

    @property
    def n_mlp_layers(self) -> int:
        """One-part layers that are a dense MLP."""
        return self.layer_kinds.count("D")

    @property
    def n_ssm_layers(self) -> int:
        """Layers that hold a recurrent state and a convolution tail."""
        if not self.has_ssm:
            return 0
        return self.layer_kinds.count("M") if self.layer_kinds else self.n_layers

    @property
    def n_kda_layers(self) -> int:
        """Layers that hold a delta rule's matrix state and its tail."""
        return self.layer_kinds.count("K")

    @property
    def has_ssm(self) -> bool:
        return self.ssm_heads > 0

    @property
    def has_kda(self) -> bool:
        return self.kda_heads > 0

    @property
    def has_state(self) -> bool:
        """A ROW holds a state beside its keys and values, which exists at
        one length only: what every refusal and every cache helper asks,
        whichever recurrence keeps it."""
        return self.has_ssm or self.has_kda

    @property
    def n_state_layers(self) -> int:
        return self.n_ssm_layers + self.n_kda_layers

    @property
    def scan_chunk(self) -> int:
        """Positions a chunk of whichever chunked recurrence the model runs."""
        return self.kda_chunk if self.has_kda else self.ssm_chunk

    def row_state_shapes(self, batch: int) -> tuple[tuple, tuple]:
        """What ``batch`` rows hold beside keys and values in ONE layer that
        keeps a state: the recurrent state's shape (float32) and the
        convolution tail's, for whichever recurrence the model runs."""
        if self.has_kda:
            p = self.kda_head_dim
            return ((batch, self.kda_heads, p, p),
                    (batch, self.kda_conv - 1, self.kda_conv_width))
        return ((batch, self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                (batch, self.ssm_conv - 1, self.ssm_conv_width))

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_conv_width(self) -> int:
        """Channels the delta layer's convolution runs over: q | k | v."""
        return 3 * self.kda_inner

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the causal convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_width(self) -> int:
        """Outputs of the mixer's in-projection: z | x | B | C | dt."""
        return self.ssm_inner + self.ssm_conv_width + self.ssm_heads

    @property
    def state_bytes_per_row(self) -> int:
        """Bytes one ROW holds beside its keys and values over every layer,
        whatever its context, with a bf16 convolution tail (delegates to
        utils.flops, which takes the tail's item size). 0 without a mixer."""
        from llm_consensus_tpu.utils.flops import state_bytes_per_row

        return state_bytes_per_row(self)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_router(self) -> int:
        """Outputs of the router: the published expert count."""
        return self.router_width or self.n_experts

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def shared_width(self) -> int:
        return self.d_shared or self.n_shared_experts * self.expert_width

    @property
    def n_expert_layers(self) -> int:
        if self.layer_kinds:
            return self.layer_kinds.count("E")
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    @property
    def cache_width(self) -> int:
        """Values the cache holds a token a layer: K and V heads, or the
        latent with its one shared rotary key."""
        if self.is_latent:
            return self.kv_lora_rank + self.qk_rope_dim
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        if self.rope_scaling is None:
            return None
        factor, low, high, orig = self.rope_scaling
        return {
            "factor": factor,
            "low_freq_factor": low,
            "high_freq_factor": high,
            "original_max_position_embeddings": orig,
        }

    def n_params(self, active_only: bool = False) -> int:
        """Exact parameter count (delegates to utils.flops — one formula,
        verified against ``init_params`` trees, serves the catalog, MFU
        accounting, and any future consumer)."""
        from llm_consensus_tpu.utils.flops import param_count

        return param_count(self, active_only=active_only)


_L = ModelConfig  # brevity in the table below

MODEL_PRESETS: dict[str, ModelConfig] = {c.name: c for c in [
    # -- Llama family ------------------------------------------------------
    _L("llama-2-7b", "llama", 32000, 4096, 32, 32, 32, 128, 11008,
       rope_theta=10000.0, max_seq_len=4096),
    _L("llama-3-8b", "llama", 128256, 4096, 32, 32, 8, 128, 14336,
       rope_theta=500000.0, max_seq_len=8192),
    _L("llama-3-70b", "llama", 128256, 8192, 80, 64, 8, 128, 28672,
       rope_theta=500000.0, max_seq_len=8192),
    _L("llama-3.1-8b", "llama", 128256, 4096, 32, 32, 8, 128, 14336,
       rope_theta=500000.0, rope_scaling=(8.0, 1.0, 4.0, 8192),
       max_seq_len=131072),
    # Llama 3.2: HF config.json dims; tied embeddings, 3.1-style rope scaling.
    _L("llama-3.2-1b", "llama", 128256, 2048, 16, 32, 8, 64, 8192,
       rope_theta=500000.0, rope_scaling=(32.0, 1.0, 4.0, 8192),
       tie_embeddings=True, max_seq_len=131072),
    _L("llama-3.2-3b", "llama", 128256, 3072, 28, 24, 8, 128, 8192,
       rope_theta=500000.0, rope_scaling=(32.0, 1.0, 4.0, 8192),
       tie_embeddings=True, max_seq_len=131072),
    # -- Mistral -----------------------------------------------------------
    _L("mistral-7b", "mistral", 32000, 4096, 32, 32, 8, 128, 14336,
       rope_theta=10000.0, sliding_window=4096, max_seq_len=32768),
    # -- Gemma -------------------------------------------------------------
    _L("gemma-7b", "gemma", 256000, 3072, 28, 16, 16, 256, 24576,
       rope_theta=10000.0, rms_eps=1e-6, activation="gelu_tanh",
       norm_offset=1.0, embed_scale=True, tie_embeddings=True),
    # -- Qwen2 -------------------------------------------------------------
    _L("qwen2-7b", "qwen2", 152064, 3584, 28, 28, 4, 128, 18944,
       rope_theta=1000000.0, rms_eps=1e-6, qkv_bias=True, max_seq_len=32768),
    _L("qwen2.5-7b", "qwen2", 152064, 3584, 28, 28, 4, 128, 18944,
       rope_theta=1000000.0, rms_eps=1e-6, qkv_bias=True, max_seq_len=131072),
    _L("qwen2.5-0.5b", "qwen2", 151936, 896, 24, 14, 2, 64, 4864,
       rope_theta=1000000.0, rms_eps=1e-6, qkv_bias=True,
       tie_embeddings=True, max_seq_len=32768),
    # -- Mixtral (MoE) -----------------------------------------------------
    _L("mixtral-8x7b", "mixtral", 32000, 4096, 32, 32, 8, 128, 14336,
       rope_theta=1000000.0, n_experts=8, experts_per_token=2,
       max_seq_len=32768),
    # -- Tiny variants: CI / CPU-mesh tests --------------------------------
    # DeepSeek-V2's block at CI size: 1 dense + 2 expert layers, 8 experts
    # in 4 groups of 2, 2 groups and 3 experts a token, 1 shared expert;
    # ranks and head sizes that are not powers of one another.
    _L("tiny-deepseek-v2", "deepseek_v2", 512, 96, 3, 4, 4, 40, 192,
       rms_eps=1e-6, n_experts=8, experts_per_token=3, d_expert=48,
       n_shared_experts=1, n_expert_groups=4, groups_per_token=2,
       routed_scale=4.0, norm_topk=False, n_dense_layers=1,
       q_lora_rank=56, kv_lora_rank=40, qk_nope_dim=24, qk_rope_dim=16,
       v_head_dim=20, rope_yarn=(8.0, 32.0, 1.0, 0.707, 0.707, 64),
       max_seq_len=4096),
    # Falcon-H1's block at CI size: a Mamba-2 mixer (4 heads of 8, state
    # 16, 2 groups) beside 4/2-head attention in each of 2 layers; every
    # multiplier differs from 1.
    _L("tiny-falcon-h1", "falcon_h1", 512, 96, 2, 4, 2, 32, 192,
       rope_theta=1e11, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
       ssm_groups=2, ssm_conv=4, ssm_chunk=8, embedding_multiplier=2.5,
       lm_head_multiplier=0.25, attention_in_multiplier=0.8,
       attention_out_multiplier=0.6, key_multiplier=0.5,
       ssm_in_multiplier=1.25, ssm_out_multiplier=0.7,
       ssm_multipliers=(0.9, 1.2, 0.75, 1.1, 0.85),
       mlp_multipliers=(0.7, 1.4), max_seq_len=4096),
    # Nemotron-H's stack at CI size: one whole period of the published
    # pattern, every layer ONE part: 5 Mamba-2 mixers (6 heads of 8, state
    # 16, 2 groups), 5 LatentMoE layers (16 ungated relu2 experts of 40 in a
    # latent of 56, 3 a token by sigmoid score + bias, one shared expert of
    # 72 on the full width) and 1 attention layer without rotary embedding.
    _L("tiny-nemotron-h", "nemotron_h", 512, 96, 11, 4, 2, 24, 0,
       activation="relu2", layer_kinds="MEMEMEM*EME", rotary=False,
       n_experts=16, experts_per_token=3, d_expert=40, n_shared_experts=1,
       d_shared=72, moe_latent=56, gated_experts=False,
       router_scoring="sigmoid_bias", routed_scale=2.5, norm_topk=True,
       ssm_heads=6, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4,
       ssm_chunk=8, max_seq_len=4096),
    # Solar-Open2's stack at CI size: one whole period of four published
    # layers as eight one-part layers: an output-gated attention layer
    # without rotary embedding, three delta-rule layers (3 heads of 16, two
    # sub-chunks a chunk, beta in (0, 2)), each followed by 16 gated experts
    # of 40, 3 a token by sigmoid score + bias, one shared expert of 40.
    _L("tiny-solar-open2", "solar_open2", 512, 96, 8, 4, 2, 24, 0,
       layer_kinds="*EKEKEKE", rotary=False, attn_out_gate=True,
       n_experts=16, experts_per_token=3, d_expert=40, n_shared_experts=1,
       router_scoring="sigmoid_bias", norm_topk=True,
       kda_heads=3, kda_head_dim=16, kda_conv=4, kda_chunk=32, kda_rank=8,
       kda_neg_eigval=True, max_seq_len=4096),
    # AFMoE's stack (Trinity-Mini) at CI size: published layers 1-5, one
    # leading dense layer and a whole period of four, as ten one-part layers
    # under the sandwich norm: window attention (window 20, rotary), a dense
    # MLP of 160, then (window, experts) (full, experts) (window, experts)
    # (window, experts); full attention applies NO rotary embedding; 4/2
    # heads of 24 with head norms and an output gate; 16 gated experts of 40,
    # 3 a token by sigmoid score + bias times 2.826, one shared expert of 40.
    _L("tiny-afmoe", "afmoe", 512, 96, 10, 4, 2, 24, 160, sliding_window=20,
       layer_kinds="WDWE*EWEWE", rotary=False, qk_norm=True, post_norm=True,
       attn_out_gate=True, embed_scale=True, n_experts=16,
       experts_per_token=3, d_expert=40, n_shared_experts=1,
       router_scoring="sigmoid_bias", routed_scale=2.826, norm_topk=True,
       max_seq_len=4096),
    _L("tiny-llama", "llama", 512, 128, 2, 4, 2, 32, 256, max_seq_len=4096),
    _L("tiny-gemma", "gemma", 512, 128, 2, 4, 4, 32, 256, activation="gelu_tanh",
       norm_offset=1.0, embed_scale=True, tie_embeddings=True, max_seq_len=4096),
    _L("tiny-qwen2", "qwen2", 512, 128, 2, 4, 2, 32, 256, qkv_bias=True,
       max_seq_len=4096),
    _L("tiny-mistral", "mistral", 512, 128, 2, 4, 2, 32, 256,
       sliding_window=32, max_seq_len=4096),
    _L("tiny-mixtral", "mixtral", 512, 128, 2, 4, 2, 32, 256,
       n_experts=4, experts_per_token=2, max_seq_len=4096),
    # -- Bench sizes: single-chip demo scale (random-init) -----------------
    _L("consensus-1b", "llama", 32000, 2048, 16, 16, 8, 128, 5632,
       rope_theta=500000.0, max_seq_len=4096),
    _L("consensus-3b", "llama", 32000, 3072, 26, 24, 8, 128, 8192,
       rope_theta=500000.0, max_seq_len=4096),
]}


def get_config(name: str, **overrides) -> ModelConfig:
    try:
        cfg = MODEL_PRESETS[name]
    except KeyError:
        raise KeyError(
            f"unknown model config {name!r}; available: {sorted(MODEL_PRESETS)}"
        ) from None
    return replace(cfg, **overrides) if overrides else cfg
