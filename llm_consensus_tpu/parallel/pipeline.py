"""GPipe-style pipeline parallelism via shard_map + ppermute.

Stages are carved from the model's layer-stacked parameter pytree
(models/transformer.py stacks every layer on a leading [L, ...] axis), so
"pipeline stage i" is literally the i-th shard of that axis over mesh axis
``pp`` — no per-stage module surgery, the same params serve TP and PP.

Schedule: classic GPipe. The batch splits into M microbatches; at micro-
step t, stage 0 feeds microbatch t while stage s runs microbatch t-s, and
activations hop stage→stage+1 over ICI with ``ppermute``. A full forward
takes M + S - 1 steps with the usual (S-1)/(M+S-1) bubble; the whole
schedule is one ``lax.scan`` of static collective-permutes, so XLA
overlaps each hop with the next stage's compute and autodiff runs the ring
backwards for free (ppermute's transpose is the reverse permute).

The reference has no model partitioning of any kind (its models are remote
APIs — SURVEY.md §2 "ABSENT" table); this is the PP half of the owed
tensor/pipeline story, composing with TP (sharding.py) on a pp×tp mesh.

**v2 schedule — boundary activations only.** v1 replicated all M
microbatch inputs to every stage and psum-broadcast the outputs, so
per-stage activation residency was O(B·T·D) and PP only sharded weights.
v2 shards both ends over the stages: each stage holds c = M/S input
microbatches and c output slots, and three things move per step —

  * the boundary activation hops stage→stage+1 (the pipeline itself);
  * the input queue rotates one stage toward stage 0, so the microbatch
    stage 0 needs at step t (global index t, stored at slot t//S of the
    stage originally holding t%S) arrives exactly on time;
  * the output queue rotates the same way, and the last stage writes
    microbatch g into slot g//S at step g+S-1 — after the remaining
    rotations it lands on stage g%S, mirroring the input layout, so the
    final outputs are stage-sharded with no gather inside the loop.

Per-stage residency is O(B·T·D/S) (the VERDICT r1 #8 criterion); the
cost is that each rotation moves both full queues (c microbatches each)
per step instead of one — 2·(M/S)× the boundary-activation traffic
itself, fully overlappable by XLA with stage compute and worth refining
to per-slot shifts if ICI ever binds. M must divide by S so the queues
are rectangular.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from llm_consensus_tpu.models.config import ModelConfig
from llm_consensus_tpu.models.transformer import _layer, embed_tokens, unembed
from llm_consensus_tpu.ops.attention import make_attention_mask
from llm_consensus_tpu.ops.rope import rope_angles, rope_inv_freq


def _pipeline_body(
    layers_local: dict,      # this stage's layer shard: leading dim L/S
    inq: jax.Array,          # [1, c, mb, T, D] — this stage's input queue
    cos: jax.Array,
    sin: jax.Array,
    mask: jax.Array,         # [mb, T, T]
    *,
    cfg: ModelConfig,
    axis_name: str,
    n_microbatches: int,
) -> jax.Array:
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = n_microbatches
    c = inq.shape[1]  # microbatches resident per stage (M/S)
    inq = inq[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_back = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def apply_stage(x):
        def scan_body(x, lp):
            x, _, _ = _layer(cfg, x, lp, cos, sin, mask, None, None, None)
            return x, None

        x, _ = jax.lax.scan(scan_body, x, layers_local)
        return x

    def step(carry, t):
        inq, outq, recv = carry
        # Stage 0 consumes global microbatch t: after t end-of-step
        # rotations, slot t//S of its queue holds exactly that element
        # (clipped reads past M are bubble-tail garbage whose results
        # never reach an output slot).
        feed = jax.lax.dynamic_index_in_dim(
            inq, jnp.clip(t // n_stages, 0, c - 1), 0, keepdims=False
        )
        x = jnp.where(stage == 0, feed, recv)
        out = apply_stage(x)
        # Rotate BEFORE the write: microbatch g (= t-(S-1)) written at
        # slot g//S then rotated T-1-t more times lands on stage g%S —
        # the mirror of the input layout. Pre-real writes (t < S-1) park
        # garbage in slot 0, which later real writes overwrite exactly
        # when their ring positions collide.
        outq = jax.lax.ppermute(outq, axis_name, perm_back)
        write_slot = jnp.clip((t - (n_stages - 1)) // n_stages, 0, c - 1)
        cur = jax.lax.dynamic_index_in_dim(outq, write_slot, 0, keepdims=False)
        newval = jnp.where(stage == n_stages - 1, out, cur)
        outq = jax.lax.dynamic_update_index_in_dim(outq, newval, write_slot, 0)
        # Boundary activation hops forward; the input queue rotates
        # toward stage 0 (end-of-step, so step t sees t rotations).
        recv = jax.lax.ppermute(out, axis_name, perm_fwd)
        inq = jax.lax.ppermute(inq, axis_name, perm_back)
        return (inq, outq, recv), None

    zero = jnp.zeros(inq.shape[1:], inq.dtype)
    init = (
        inq,
        jnp.zeros_like(inq),  # varying by construction (from sharded inq)
        jax.lax.pcast(zero, axis_name, to="varying"),
    )
    (_, outq, _), _ = jax.lax.scan(step, init, jnp.arange(m + n_stages - 1))
    # Outputs end stage-sharded: stage s holds {g : g ≡ s (mod S)} at
    # slot g//S — returned with a leading stage axis, no gather here.
    return outq[None]


def pipeline_forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,          # [B, T] int32
    mesh: Mesh,
    axis_name: str = "pp",
    microbatches: Optional[int] = None,
) -> jax.Array:
    """Training/eval forward with layers pipelined over ``axis_name``.

    Returns logits [B, T, V] fp32, numerically equal to
    ``models.forward(params, cfg, tokens)`` (same layer math, same order).
    Constraints: n_layers divisible by the stage count, batch by the
    microbatch count, and microbatches by the stage count (stage-resident
    queues). Default microbatches: max(4, stage count).
    """
    n_stages = mesh.shape[axis_name]
    if microbatches is None:
        # Smallest multiple of the stage count that is >= 4 (the M % S
        # constraint must hold for ANY stage count, including e.g. 3).
        microbatches = n_stages * max(1, -(-4 // n_stages))
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible by {n_stages} stages")
    b, t = tokens.shape
    if b % microbatches:
        raise ValueError(f"batch {b} not divisible by {microbatches} microbatches")
    if microbatches % n_stages:
        raise ValueError(
            f"{microbatches} microbatches not divisible by {n_stages} stages "
            "(the v2 schedule keeps M/S microbatches resident per stage)"
        )
    mb = b // microbatches
    c = microbatches // n_stages

    x = embed_tokens(params, cfg, tokens)

    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (mb, t))
    inv_freq = rope_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling_dict)
    cos, sin = rope_angles(positions, inv_freq)
    mask = make_attention_mask(positions, positions, None, cfg.sliding_window)

    # Stage-sharded input layout: global microbatch g lives on stage
    # g % S at slot g // S — [S, c, mb, T, D] with axis 0 over ``pp``,
    # so each stage holds only its c microbatches (1/S of the batch).
    xs = x.reshape(microbatches, mb, t, cfg.d_model)
    xs = xs.reshape(c, n_stages, mb, t, cfg.d_model).swapaxes(0, 1)

    layer_specs = jax.tree.map(lambda _: P(axis_name), params["layers"])
    body = jax.shard_map(
        partial(
            _pipeline_body, cfg=cfg, axis_name=axis_name,
            n_microbatches=microbatches,
        ),
        mesh=mesh,
        in_specs=(layer_specs, P(axis_name), P(), P(), P()),
        out_specs=P(axis_name),
    )
    ys = body(params["layers"], xs, cos, sin, mask)

    # Undo the stage-sharded layout: [S, c, ...] → global microbatch
    # order g = slot·S + stage (one resharding collective, outside the
    # pipeline loop).
    ys = ys.swapaxes(0, 1).reshape(b, t, cfg.d_model)
    return unembed(params, cfg, ys)


def dryrun_pipeline(n_devices: int, devices=None) -> None:
    """One pipelined train step on tiny shapes (driver's pp validation)."""
    import optax

    from llm_consensus_tpu.models import get_config, init_params
    from llm_consensus_tpu.parallel.mesh import make_mesh
    from llm_consensus_tpu.train.loss import cross_entropy_loss

    devices = list(devices if devices is not None else jax.devices())[:n_devices]
    # Stage count = largest power of two ≤ n_devices that divides n_layers.
    cfg = get_config("tiny-llama", n_layers=8)
    pp = 1
    while pp * 2 <= min(n_devices, cfg.n_layers) and cfg.n_layers % (pp * 2) == 0:
        pp *= 2
    mesh = make_mesh({"pp": pp}, devices[:pp])
    microbatches = max(4, pp)  # v2 needs M % S == 0

    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size, jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state):
        def loss_fn(p):
            logits = pipeline_forward(
                p, cfg, tokens, mesh, microbatches=microbatches
            )
            return cross_entropy_loss(logits, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    params, opt_state, loss = train_step(params, opt_state)
    loss = float(loss)
    assert jnp.isfinite(loss), "pipeline: non-finite loss"
    print(
        f"[dryrun] pipeline pp={pp} microbatches={microbatches} "
        f"loss={loss:.4f} ok"
    )
