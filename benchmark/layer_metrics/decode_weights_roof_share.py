"""Kernels: a weights-only roofline share of the judge model's decode
step: (its weight bytes as stored / the device kind's peak bytes per
second) / decode_dev_ms_per_step. Named for what it is: the least time the
step could take if it only streamed its weights once, over the time it
took. Key/value reads and compute are not in the numerator. With the model
sharded over n chips each streams 1/n of the bytes.

It counts a DENSE block, every weight streamed once a step, so
``BENCHMARK.json`` lists the cells it is read in. A cell of another family
(experts of which a step streams only those its rows chose, a latent cache,
recurrent state) brings a reader and a count of bytes of its own."""

from benchmark.layer_metrics import decode_dev_ms_per_step

DENSE_FAMILIES = ("qwen2", "mistral")


def weight_bytes(spec: dict, stored: str) -> float:
    """Bytes of one dense model as the program stores it: matmul weights in
    the stated type (int8: one byte, plus a bf16 scale per output channel),
    the embedding and the norms in bf16. Raises for an entry it cannot
    count, rather than count a dense block of its ``d_ff``."""
    if spec["family"] not in DENSE_FAMILIES or spec.get("more_fields"):
        raise ValueError(
            f"decode_weights_roof_share counts dense blocks of {DENSE_FAMILIES} "
            f"with no more_fields, not family {spec['family']!r} with "
            f"{sorted(spec.get('more_fields') or {})}")
    d, f, l, v = spec["d_model"], spec["d_ff"], spec["n_layers"], spec["vocab_size"]
    q, kv = spec["n_heads"] * spec["head_dim"], spec["n_kv_heads"] * spec["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    out_channels = l * (q + 2 * kv + d + 2 * f + d)
    head = 0 if spec["tie_embeddings"] else d * v
    if not spec["tie_embeddings"]:
        out_channels += v
    matmul = l * per_layer + head
    other = v * d + (2 * l + 1) * d + (l * (q + 2 * kv) if spec["qkv_bias"] else 0)
    if stored == "int8":
        return matmul * 1.0 + out_channels * 2.0 + other * 2.0
    return (matmul + other) * 2.0


def read(ctx):
    step_ms = decode_dev_ms_per_step.read(ctx)
    if not step_ms or ctx.get("peaks") is None:
        return None
    cfg = ctx["config"]
    engines = (ctx["stats_after"].get("device") or {}).get("engines") or {}
    n_chips = len((engines.get(cfg["judge"]) or {}).get("devices") or [0])
    least_ms = (
        weight_bytes(cfg["models"][cfg["judge"]], cfg["weights"]) / n_chips
        / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    )
    return least_ms / step_ms * 100.0
