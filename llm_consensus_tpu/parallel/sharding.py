"""GSPMD sharding specs for model params and KV caches.

Megatron-style tensor parallelism expressed as `PartitionSpec` trees that
mirror ``models.transformer.init_params`` exactly: QKV projections are
column-parallel (heads sharded over ``tp``), the output projection is
row-parallel, the MLP shards its hidden dim, and MoE experts shard over the
expert axis (``ep`` if the mesh has one, else ``tp``). XLA/GSPMD inserts
the (all-reduce after row-parallel matmuls, all-to-alls at MoE dispatch)
collectives — this module only declares placements; there are no explicit
collectives on this path.

The reference has no analog (its compute is three HTTP clients —
/root/reference/internal/provider/{openai,anthropic,google}.go); this is
what "a model bigger than one chip" requires instead.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_consensus_tpu.models.config import ModelConfig


def _axis(mesh: Optional[Mesh], name: str, dim: int) -> Optional[str]:
    """Use mesh axis ``name`` for a tensor dim only if valid & divisible."""
    if mesh is None or name not in mesh.axis_names:
        return None
    size = mesh.shape[name]
    if size == 1 or dim % size != 0:
        return None
    return name


def param_specs(cfg: ModelConfig, mesh: Optional[Mesh] = None) -> dict:
    """PartitionSpec pytree matching ``init_params(cfg)``.

    ``mesh=None`` returns the canonical (unsanitized) specs; with a mesh,
    any dim not divisible by its axis size degrades to replicated so the
    same code serves tp=1 (single chip) through tp=16 without special
    cases.
    """
    dh = cfg.head_dim
    tp_q = _axis(mesh, "tp", cfg.n_heads * dh)
    tp_kv = _axis(mesh, "tp", cfg.n_kv_heads * dh)
    tp_ff = _axis(mesh, "tp", cfg.d_ff)
    tp_vocab = _axis(mesh, "tp", cfg.vocab_size)
    if mesh is not None and all(
        dict(mesh.shape).get(a, 1) > 1 for a in ("dp", "tp", "sp")
    ):
        # jax 0.4.x GSPMD miscompiles the fwd+bwd train step on 3-axis
        # dp×tp×sp meshes when the embedding table is vocab-sharded over
        # tp: the loss computed inside value_and_grad diverges from the
        # identical forward-only program by ~2e-3 RELATIVE in fp32 (not
        # reassociation ulps — the forward alone matches to 1e-7, and
        # every 2-axis sub-mesh of the same factors is exact). Bisected
        # to the embed/lm_head specs: replicating either the vocab
        # sharding or the attention projections restores exactness, and
        # replicating the (small) vocab table is the cheap one. Same
        # failure class as the non-dividing-tp qkv pin in
        # models/transformer.py — a version-scoped workaround, keyed on
        # exactly the miscompiling mesh shape so inference meshes
        # (tp-only, tp×sp, dp×tp) keep the sharded LM head.
        tp_vocab = None
    if cfg.layer_kinds:
        # Every layer one part, a stack a kind. The model runs on one chip
        # (a state-space model's engine refuses a mesh with tp > 1): every
        # leaf whole, whatever its rank.
        from llm_consensus_tpu.models import init_params

        shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        return jax.tree.map(lambda leaf: P(*(None,) * leaf.ndim), shapes)

    def stack(routed: bool) -> dict:
        layers: dict = {"attn_norm": P(None, None), "mlp_norm": P(None, None)}
        if cfg.is_latent:
            # A latent (MLA) model runs on one chip (a mesh with tp or ep
            # > 1 is refused when its engine is built): every leaf whole.
            layers.update({
                name: P(None, None, None)
                for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
            })
            layers.update({"q_norm": P(None, None), "kv_norm": P(None, None)})
        else:
            layers.update({
                "wq": P(None, None, tp_q),
                "wk": P(None, None, tp_kv),
                "wv": P(None, None, tp_kv),
                "wo": P(None, tp_q, None),
            })
        if cfg.has_ssm:
            # A state-space model runs on one chip (a mesh with tp > 1 is
            # refused when its engine is built): the mixer's leaves whole.
            layers.update({
                name: P(None, None, None)
                for name in ("ssm_in", "ssm_conv", "ssm_out")
            })
            layers.update({
                name: P(None, None) for name in (
                    "ssm_conv_bias", "ssm_dt_bias", "ssm_a_log", "ssm_d",
                    "ssm_norm")
            })
        if cfg.qkv_bias:
            layers["bq"] = P(None, tp_q)
            layers["bk"] = P(None, tp_kv)
            layers["bv"] = P(None, tp_kv)
        if routed:
            ep_name = "ep" if (mesh is None or "ep" in mesh.axis_names) else "tp"
            ep = _axis(mesh, ep_name, cfg.n_experts)
            layers["w_router"] = P(None, None, None)
            # Experts shard over ep; each expert's hidden dim additionally
            # shards over tp when both axes exist (ep×tp 2-D sharding).
            inner = _axis(mesh, "tp", cfg.expert_width) if ep != "tp" else None
            layers["w_gate"] = P(None, ep, None, inner)
            layers["w_up"] = P(None, ep, None, inner)
            layers["w_down"] = P(None, ep, inner, None)
            if cfg.n_shared_experts:
                tp_fs = _axis(mesh, "tp", cfg.n_shared_experts * cfg.expert_width)
                layers["ws_gate"] = P(None, None, tp_fs)
                layers["ws_up"] = P(None, None, tp_fs)
                layers["ws_down"] = P(None, tp_fs, None)
        else:
            layers["w_gate"] = P(None, None, tp_ff)
            layers["w_up"] = P(None, None, tp_ff)
            layers["w_down"] = P(None, tp_ff, None)
        return layers

    specs = {
        "embed": P(tp_vocab, None),
        "final_norm": P(None),
        "layers": stack(cfg.is_moe),
    }
    if cfg.is_moe and cfg.n_dense_layers:
        specs["layers_dense"] = stack(False)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, tp_vocab)
    return specs


def opt_moment_specs(cfg: ModelConfig, mesh: Optional[Mesh] = None) -> dict:
    """Cross-replica specs for optimizer moment buffers (ZeRO-1-style).

    Each AdamW moment mirrors its param's tensor-parallel spec, then its
    first still-replicated dim that ``dp`` divides additionally shards
    over ``dp`` — the weight-update state partitions across data-parallel
    replicas instead of being mirrored into every one (the automatic
    cross-replica-sharding scheme: moments are 2/3 of AdamW state, so at
    dp=8 this drops that slice's residency ~8×; GSPMD inserts the
    reduce-scatter/all-gather pair around the update). Wherever no dim
    divides, the moment stays on the plain param spec — same degradation
    contract as :func:`param_specs`.
    """
    from llm_consensus_tpu.models import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, mesh)
    dp = (
        mesh.shape["dp"]
        if mesh is not None and "dp" in mesh.axis_names
        and mesh.shape["dp"] > 1 else None
    )

    def widen(leaf, spec):
        if dp is None:
            return spec
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, ax in enumerate(entries):
            if ax is None and leaf.shape[i] % dp == 0:
                entries[i] = "dp"
                return P(*entries)
        return spec

    return jax.tree.map(widen, shapes, specs)


def cache_specs(cfg: ModelConfig, mesh: Optional[Mesh] = None, batch: int = 1) -> dict:
    """PartitionSpec pytree matching ``init_kv_cache``: [L, B, S, Hkv, dh].

    KV heads shard with the attention TP split; batch shards over dp when
    it divides (decode streams are batch=1, so dp stays replicated there).
    A latent model's one leaf ``kv`` has a single shared head: never split.
    A state-space model's per-row state leaves (``STATE_KEY``) split over
    rows alone.
    """
    from llm_consensus_tpu.ops.quant import STATE_KEY

    dp = _axis(mesh, "dp", batch)
    if cfg.is_latent:
        return {"kv": P(None, dp, None, None, None)}
    tp_kv = _axis(mesh, "tp", cfg.n_kv_heads)
    spec = P(None, dp, None, tp_kv, None)
    specs = {"k": spec, "v": spec}
    if cfg.has_state:
        specs[STATE_KEY] = {
            "state": P(None, dp, None, None, None),
            "conv": P(None, dp, None, None),
        }
    return specs


def abstract_param_bytes(cfg: ModelConfig, mesh: Mesh) -> tuple[int, int]:
    """(total_bytes, tp_sharded_bytes) of ``cfg``'s parameter tree on
    ``mesh`` — shapes and specs only, nothing materialized.

    The placement-feasibility primitive for big models: a 70B judge's
    residency math (does it fit at tp=8? at int8?) must be answerable
    without 140 GB of HBM. Also validates that every sharded spec is
    constructible on the mesh.
    """
    import jax

    from llm_consensus_tpu.models import init_params

    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = param_specs(cfg, mesh)
    acc = {"total": 0, "sharded": 0}

    def tally(leaf, spec):
        nbytes = leaf.size * leaf.dtype.itemsize
        acc["total"] += nbytes
        if any(ax is not None for ax in spec):
            NamedSharding(mesh, spec)  # constructible on this mesh
            acc["sharded"] += nbytes

    # tree.map (not a leaves zip): a param present in init_params but
    # missing from param_specs — or vice versa — must error loudly, not
    # silently misalign the byte accounting.
    jax.tree.map(tally, shapes, specs)
    return acc["total"], acc["sharded"]


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> dict:
    """``param_specs`` as ``NamedSharding``s on ``mesh``: what
    ``init_params(shardings=...)`` makes each leaf under, so a tree larger
    than one chip never exists whole on any chip."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(cfg, mesh),
        is_leaf=lambda s: isinstance(s, P),
    )


def shard_pytree(tree, specs, mesh: Mesh):
    """Place ``tree`` on ``mesh`` according to a matching spec pytree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache) -> dict:
    """``NamedSharding``s for a tree shaped like ``init_kv_cache``'s
    (arrays or their shapes): what ``Engine.new_cache`` makes a cache
    under, and what ``make_shard_fn`` moves one to.

    int8 caches nest {"q8", "s"} under k/v: codes keep the
    [L, B, S, Hkv, dh] layout; scales are seq-minor [L, B, Hkv, S] (heads
    on axis 2), so their tp split moves with the head axis. Layout
    discrimination routes through ops.quant.kv_seq_axis, the rule's
    single owner."""
    from llm_consensus_tpu.ops.quant import kv_seq_axis, kv_tree_map

    k_spec = next(iter(cache_specs(cfg, mesh).values()))
    s_spec = P(k_spec[0], k_spec[1], k_spec[3], k_spec[2])
    return kv_tree_map(
        lambda leaf: NamedSharding(
            mesh, k_spec if kv_seq_axis(leaf) == 2 else s_spec
        ),
        cache,
        # per-row state: layers, rows (as the slot leaves split them), whole
        state=lambda leaf: NamedSharding(
            mesh, P(k_spec[0], k_spec[1], *(None,) * (leaf.ndim - 2))),
    )


def make_shard_fn(cfg: ModelConfig, mesh: Mesh) -> Callable:
    """Shard fn for ``engine.Engine(shard_fn=...)``.

    Dispatches on pytree shape: the params tree (has ``embed``) gets
    ``param_specs``, the KV cache (``k``/``v``, or a latent model's ``kv``;
    a state-space model's state leaves beside them) gets ``cache_specs``.
    """

    def shard(tree):
        if isinstance(tree, dict) and "embed" in tree:
            return shard_pytree(tree, param_specs(cfg, mesh), mesh)
        if isinstance(tree, dict) and {"k", "v", "kv"} & set(tree):
            return jax.tree.map(
                jax.device_put, tree, cache_shardings(cfg, mesh, tree)
            )
        raise ValueError(f"unrecognized pytree with keys {list(tree)}")

    return shard
