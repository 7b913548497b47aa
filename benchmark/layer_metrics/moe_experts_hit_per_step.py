"""Model step: held routed experts that took at least one row, an expert
layer a decode step: d moe_expert_reads / d moe_layer_steps of the judge
pool (/statsz batchers; the decode chunks' own sums, fetched with their
tokens). What a step streams of the experts held here: about 4 of 20 at six
rows if routing is even. Nothing to read from a program without the
counters."""

from benchmark.layer_metrics import latent_moe_decode_roofline


def read(ctx):
    counted = latent_moe_decode_roofline.counters(ctx)
    return None if counted is None else counted[0]
