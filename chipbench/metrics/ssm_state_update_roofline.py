"""ssm_state_update_roofline (%): the least time the chip could take to
move the recurrent state the window's work had to move (a decode row
reads and writes its float32 SSM state and its conv rows in every Mamba
block; an admission writes them once) over the device time of every
instruction that takes or produces one of the two state pools. The
update is left to XLA, whose fusions carry no scope name
(`trace.scope_seconds` finds nothing under `decode.ssm_update`), so the
instructions are found by the pools' types, which the configuration and
the traffic's slots fix: a second read of the state, or a copy of a
pool, lands in the time and lowers the share. Layer: kernels. Source:
device trace. Moves serve_tokens_per_s. Bound by memory bandwidth."""
from chipbench import flops_nemotron_h as fl
from chipbench import trace
from chipbench.peaks import least_seconds


def read(view):
    o, cfg = view.observed, view.cfg
    if "mamba_num_heads" not in cfg:
        return None
    pools = fl.state_pool_shapes(cfg, o["slots"])
    spent = sum(secs for text, secs in trace.leaf_ops(view.summary).items()
                if any(pool in text for pool in pools))
    if spent <= 0.0:
        return None
    moved = fl.sizes(cfg)["n_m"] * (
        o["decode_rows"] * fl.state_bytes_per_row(cfg)
        + o["prefills"] * fl.state_bytes_per_admission(cfg))
    return 100.0 * least_seconds(0, moved, view.peak) / spent
