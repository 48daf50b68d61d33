"""Tensors of one upload, built from a configuration file's sizes.

A configuration names a model's published sizes under its Hugging Face
``config.json`` keys. One upload is one safetensors shard that holds
``num_hidden_layers`` whole decoder layers in Hugging Face naming and module
order. A configuration with ``num_local_experts`` has Mixtral's sparse expert
block; one without has a dense gated MLP. Extra keys of this benchmark's own:
``attention_bias`` (q/k/v biases, as Qwen2 has), ``norm_dtype`` (safetensors
tag of the RMSNorm weights) and ``weight_dtype``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

ITEMSIZE = {"BF16": 2, "F16": 2, "F32": 4}


class Tensor(NamedTuple):
    name: str
    shape: Tuple[int, ...]
    dtype: str  # safetensors tag

    @property
    def numel(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def nbytes(self) -> int:
        return self.numel * ITEMSIZE[self.dtype]


def layer_tensors(cfg: Dict) -> List[Tensor]:
    """Every tensor of one upload, in Hugging Face naming and order."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or d // heads
    q, kv = heads * head_dim, cfg["num_key_value_heads"] * head_dim
    w, norm = cfg.get("weight_dtype", "BF16"), cfg.get("norm_dtype", "BF16")
    bias = bool(cfg.get("attention_bias", False))
    out: List[Tensor] = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        for proj, rows in (("q_proj", q), ("k_proj", kv), ("v_proj", kv)):
            out.append(Tensor(p + f"self_attn.{proj}.weight", (rows, d), w))
            if bias:
                out.append(Tensor(p + f"self_attn.{proj}.bias", (rows,), w))
        out.append(Tensor(p + "self_attn.o_proj.weight", (d, q), w))
        n_exp = cfg.get("num_local_experts")
        if n_exp:
            m = p + "block_sparse_moe."
            out.append(Tensor(m + "gate.weight", (n_exp, d), w))
            for e in range(n_exp):
                out += [Tensor(m + f"experts.{e}.w1.weight", (f, d), w),
                        Tensor(m + f"experts.{e}.w2.weight", (d, f), w),
                        Tensor(m + f"experts.{e}.w3.weight", (f, d), w)]
        else:
            out += [Tensor(p + "mlp.gate_proj.weight", (f, d), w),
                    Tensor(p + "mlp.up_proj.weight", (f, d), w),
                    Tensor(p + "mlp.down_proj.weight", (d, f), w)]
        out += [Tensor(p + "input_layernorm.weight", (d,), norm),
                Tensor(p + "post_attention_layernorm.weight", (d,), norm)]
    return out
