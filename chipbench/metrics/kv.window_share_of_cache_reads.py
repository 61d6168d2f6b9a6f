"""kv.window_share_of_cache_reads (%): of the K and V bytes that the
window's decode steps' attention read, the share that the window layers
read: their layers x the keys they attended (at most `sliding_window` a
row) x their row's bytes, over the same of both kinds, at the published
widths. About a tenth where full layers read contexts of thousands; a
window layer that read whole sequences would bring it to its share of
the layers' rows (83 % in a 5 : 2 cut with twice the KV heads). Layer:
cache. Source: the chunk counters `attn_tokens_full` and
`attn_tokens_window` in the `serve:commit` spans' metadata. Moves
serve_tokens_per_s."""
from chipbench import flops_mimo_v2 as fl
from chipbench import spans_mimo_v2 as counters


def read(view):
    cfg = view.cfg
    if "hybrid_layer_pattern" not in cfg:
        return None
    c = counters.window_counts(view)
    if c is None:
        return None
    z = fl.sizes(cfg)
    ring = z["n_window"] * c["ring_keys"] * fl.kv_row_bytes(cfg, fl.WINDOW)
    full = z["n_full"] * c["full_keys"] * fl.kv_row_bytes(cfg, fl.FULL)
    return 100.0 * ring / (ring + full) if ring + full else None
