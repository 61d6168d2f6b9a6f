"""moe.train_expert_load_max_over_mean (ratio): how uneven a train
step's routing over the experts held here is: the largest number of rows
one expert got in one sparse layer (`moe_max_load`) over the mean rows of
an expert that got any (`moe_pairs_here` / `moe_experts_touched`, both
summed over the sparse layers); the mean over the window's steps. 1 would
be perfectly even. Layer: expert layer. Source: the step's device-side
counters in the `train_step:call` spans' metadata. Moves
train_tokens_per_s (the largest group bounds a grouped product's tail)."""
from chipbench import spans_lfm2


def read(view):
    if view.cfg.get("model_type") != "lfm2_moe":
        return None
    steps = spans_lfm2.window_steps(view)
    ratios = [peak * touched / pairs for pairs, touched, peak in steps or []
              if pairs and touched]
    return sum(ratios) / len(ratios) if ratios else None
