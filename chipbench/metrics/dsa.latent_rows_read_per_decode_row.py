"""dsa.latent_rows_read_per_decode_row (rows): latent rows one layer's
decode attention read per decode row of the window: `index_topk`
(2,048) where the attention reads only what the indexer chose and every
context is longer, the context's length (8k to 16k here) where it reads
everything. Layer: cache. Source: the chunk counters `latent_rows_read`
and `attn_rows` in the `serve:commit` spans' metadata. Moves
serve_tokens_per_s."""
from chipbench import spans_deepseek_v32 as counters


def read(view):
    if "index_topk" not in view.cfg:
        return None
    c = counters.latent_counts(view)
    if c is None or not c["rows"]:
        return None
    return c["rows_read"] / view.observed["decode_rows"]
