"""sparse_mla_prefill_roofline (%): the least time the chip could take
for the attention of the prompts prefilled in the serve window in the
expanded form, at the pairs the indexer chose (min(t + 1, 2,048) keys a
query; 81,920 FLOP a pair at the published widths) over the device time
of the instructions under the `prefill.attend` scope. The kernel attends
every causal key of a chunk masked to the choice and forms the heads'
keys and values from the latent rows itself, so it reads low here by
design. Layer: kernels. Source: device trace; the prompts from the
harness's count. Moves serve_tokens_per_s."""
from chipbench import flops_deepseek_v32 as fl
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPE = "prefill.attend"


def read(view):
    o, cfg = view.observed, view.cfg
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0 or "index_topk" not in cfg or not o["prefill_tokens"]:
        return None
    work, moved = fl.prefill_attention(
        cfg, o["prefill_tokens"],
        fl.chosen_pairs(cfg, o["prefills"], o["prefill_tokens"]))
    return 100.0 * least_seconds(work, moved, view.peak) / spent
