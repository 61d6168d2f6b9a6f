"""The system under test for `lfm2_moe` configurations: builds the
program's own `Lfm2ForCausalLM` and `pt.jit.TrainStep` from a
configuration file and hands them the benchmark's seeded weights.

The reference's leaves and the program's parameters and buffers carry the
same names and shapes ([in, out] matrices, [experts held, in, out]
stacks), so the seeded arrays become the model's own as they are: no
second copy on the device. Only this module (and the driver loops in
`chipbench/kinds/`) imports the program. It reads program internals in
one place, as `adapters/llama_dense.py` does: the optimizer's
accumulators (`opt._accumulators`).
"""
from __future__ import annotations

import gc

# at import, so that a program without this family fails the cell at
# once (ImportError, before any weight is made) rather than after set-up
from paddle_tpu.models import lfm2 as program


def program_config(cfg):
    """The program's configuration from a configuration file's dict: the
    published keys under their own names; the router keeps its published
    width and the file's `num_experts` says how many experts are held
    here, from `experts_first`."""
    published = cfg.get("published", {})
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[cfg["torch_dtype"]]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "layer_types", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "conv_bias", "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
            "norm_eps", "rope_theta", "max_position_embeddings")
    return program.Lfm2Config(
        **{k: cfg[k] for k in keys if k in cfg},
        num_experts=published.get("num_experts", cfg["num_experts"]),
        experts_held=(cfg.get("experts_first", 0), cfg["num_experts"]),
        dtype=dtype)


class Trainer:
    """`pt.jit.TrainStep` over the model with the traffic's optimizer."""

    def __init__(self, cfg, traffic, weights):
        import paddle_tpu as pt
        self.model = program.Lfm2ForCausalLM(program_config(cfg),
                                             arrays=weights)
        crit = program.Lfm2PretrainingCriterion()
        o = traffic["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"unknown optimizer {o['name']!r}")
        if traffic.get("recompute"):
            raise NotImplementedError("recomputation")
        self.opt = pt.optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"],
            parameters=self.model.parameters(),
            moment_dtype=o.get("moment_dtype"))
        self.step = pt.jit.TrainStep(self.model,
                                     lambda lg, lb: crit(lg, lb), self.opt)
        self._pt = pt
        self._params = {name: getattr(self.model, attr)
                        for name, attr in self.model._names.items()}
        self._trained = {id(p) for p in self.model.parameters()}

    def __call__(self, ids, labels):
        """One step on host arrays ids, labels [B, S]; returns the loss
        as a device array (not waited for)."""
        pt = self._pt
        loss = self.step((pt.to_tensor(ids, dtype="int64"),),
                         (pt.to_tensor(labels, dtype="int64"),))
        return loss._data

    def param(self, leaf):
        return self._params[leaf]._data

    def moment1(self, leaf):
        """The first moment of `leaf`; of the routers' fixed bias, which
        the program keeps as a buffer with no optimizer state, zeros (its
        gradient as the optimizer got it)."""
        p = self._params[leaf]
        if id(p) not in self._trained:
            return self._pt.zeros_like(p)._data
        return self.opt._accumulators[("moment1", id(p))]

    def close(self):
        self.step = self.opt = self.model = self._params = None
        gc.collect()
