"""moe.expert_load_max_over_mean.mimo_v2 (ratio): how uneven the routing
of decode rows over the experts held here is in a `mimo_v2`
configuration: per chunk, the largest number of rows one expert got in
one step of one expert layer (`moe_max_load`) over the mean rows of an
expert that got any (`moe_pairs_here` / `moe_experts_touched`); the mean
over the window's chunks. 1 would be perfectly even. Layer: expert
layer. Source: the chunk counters in the `serve:commit` spans' metadata.
Moves serve_tokens_per_s (the largest group bounds a grouped product's
tail)."""
from chipbench import spans_mimo_v2 as counters


def read(view):
    if "hybrid_layer_pattern" not in view.cfg:
        return None
    c = counters.window_counts(view)
    if c is None:
        return None
    ratios = [peak * touched / pairs for peak, pairs, touched in c["chunks"]
              if pairs and touched]
    return sum(ratios) / len(ratios) if ratios else None
