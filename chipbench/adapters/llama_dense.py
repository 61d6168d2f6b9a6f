"""The system under test for dense Llama-style configurations: builds the
program's own model, `pt.jit.TrainStep` and `PagedDecoder` from a
configuration file and hands them the benchmark's seeded weights.

Only this module (and the driver loops in `chipbench/kinds/`) imports
the program. It reads program internals in three places, each named in
`PERF.md`'s Open questions as something a later PR should give a public
face: parameter arrays (`p._data`), the optimizer's accumulators
(`opt._accumulators`) and the decoder's slots (`dec._slots`).
"""
from __future__ import annotations

import gc

# reference leaf name (chipbench/reference/llama_dense.py) -> program name
_LAYER = {"ln1": "input_layernorm", "wq": "self_attn.q_proj",
          "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
          "wo": "self_attn.o_proj", "ln2": "post_attention_layernorm",
          "wg": "mlp.gate_proj", "wu": "mlp.up_proj", "wd": "mlp.down_proj"}


def program_name(leaf):
    if leaf == "embed":
        return "llama.embed_tokens.weight"
    if leaf == "norm":
        return "llama.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, index, kind = leaf.split(".")
    return f"llama.layers.{index}.{_LAYER[kind]}.weight"


def build_model(cfg, weights):
    """The program's LlamaForCausalLM at the configuration's sizes, its
    parameters replaced by the benchmark's seeded ones."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[cfg["torch_dtype"]]
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        head_dim=cfg.get("head_dim"), dtype=dtype))
    params = dict(model.named_parameters())
    missing = set(params) - {program_name(k) for k in weights}
    if missing:
        raise KeyError(f"no seeded weights for {sorted(missing)}")
    for leaf, array in weights.items():
        p = params[program_name(leaf)]
        if tuple(p.shape) != tuple(array.shape) or \
                p._data.dtype != array.dtype:
            raise ValueError(f"{leaf}: seeded {array.shape} {array.dtype} "
                             f"vs program {p.shape} {p._data.dtype}")
        p._data = array
    return model


class Trainer:
    """`pt.jit.TrainStep` over the model with the traffic's optimizer."""

    def __init__(self, cfg, traffic, weights):
        import paddle_tpu as pt
        from paddle_tpu.models import LlamaPretrainingCriterion
        self.leaves = list(weights)
        self.model = build_model(cfg, weights)
        crit = LlamaPretrainingCriterion(self.model.config)
        o = traffic["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"unknown optimizer {o['name']!r}")
        self.opt = pt.optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"],
            parameters=self.model.parameters(),
            moment_dtype=o.get("moment_dtype"))
        self.step = pt.jit.TrainStep(self.model,
                                     lambda lg, lb: crit(lg, lb), self.opt)
        self._pt = pt
        self._params = dict(self.model.named_parameters())

    def __call__(self, ids, labels):
        """One step on host arrays ids, labels [B, S]; returns the loss
        as a device array (not waited for)."""
        pt = self._pt
        loss = self.step((pt.to_tensor(ids, dtype="int64"),),
                         (pt.to_tensor(labels, dtype="int64"),))
        return loss._data

    def param(self, leaf):
        return self._params[program_name(leaf)]._data

    def moment1(self, leaf):
        p = self._params[program_name(leaf)]
        return self.opt._accumulators[("moment1", id(p))]

    def close(self):
        self.step = self.opt = self.model = self._params = None
        gc.collect()


def build_decoder(cfg, traffic, weights):
    """`PagedDecoder` with the traffic's slots, block and pool; the
    model is dropped once the decoder holds its stacked weights."""
    from paddle_tpu.models.paged_decode import PagedDecoder
    model = build_model(cfg, weights)
    dec = PagedDecoder(model, max_len=traffic["max_len"],
                       block_size=traffic["block"],
                       num_blocks=traffic["pool_blocks"],
                       max_slots=traffic["slots"])
    del model
    gc.collect()
    return dec
