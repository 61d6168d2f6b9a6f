"""Hardware roofline constants: peak FLOPs per chip and the model-FLOPs
formula used for MFU accounting (bench.py and TrainStep telemetry share
these so BENCH artifacts and the registry agree on what 'MFU' means)."""
from __future__ import annotations

__all__ = ["PEAK_FLOPS", "peak_flops", "model_flops_per_token"]

PEAK_FLOPS = {
    # bf16 peak per chip, by device_kind substring
    "v6": 918e12, "v5p": 459e12, "v5": 197e12, "v4": 275e12, "v3": 123e12,
}


def peak_flops(device) -> float | None:
    """Peak bf16 FLOPs/s for a jax device, or None for a device the table
    does not know: no peak means no MFU, never another chip's."""
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    return None


def model_flops_per_token(cfg, seq_len: int, n_params: int) -> float:
    """6N (fwd+bwd matmuls) + 12*L*(nh*hd)*s attention term (PaLM appendix
    formula; nh*hd == hidden for standard configs, and stays correct for
    head-sharded per-chip models where attention width != hidden)."""
    attn_width = cfg.num_attention_heads * cfg.head_dim
    return 6.0 * n_params + 12.0 * cfg.num_hidden_layers * attn_width \
        * seq_len
