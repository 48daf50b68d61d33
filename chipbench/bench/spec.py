"""Tensors of one upload, built from a configuration file's sizes.

A configuration names a model's published sizes under its Hugging Face
``config.json`` keys. One upload is one safetensors shard that holds
``num_hidden_layers`` whole decoder layers in Hugging Face naming and module
order. The layer's layout is that of the configuration's ``model_type``:
``layouts/<model_type>.py``, found by name (``registry.layout``). A
configuration without ``model_type`` (the tests' tiny ones) takes Mixtral's
layout where it has ``num_local_experts`` and Qwen2's otherwise.

Extra keys of this benchmark's own: ``attention_bias`` (q/k/v biases, as
Qwen2 has), ``norm_dtype`` (safetensors tag of the RMSNorm weights) and
``weight_dtype``. A ``quantization_config`` as published for FP8 checkpoints
(``quant_method`` ``fp8``, ``fmt`` ``e4m3``, ``weight_block_size``) stores
every weight a layout makes with ``linear`` as ``F8_E4M3``, followed by its
F32 ``weight_scale_inv``: one scale per block, blocks at the edges partial.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import registry

ITEMSIZE = {"BF16": 2, "F16": 2, "F32": 4, "F8_E4M3": 1}


class Tensor(NamedTuple):
    name: str
    shape: Tuple[int, ...]
    dtype: str  # safetensors tag
    scale_of: str = ""              # a weight_scale_inv: the weight it scales
    block: Tuple[int, int] = (0, 0)  # ... and the (rows, cols) of each scale's block

    @property
    def numel(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def nbytes(self) -> int:
        return self.numel * ITEMSIZE[self.dtype]


def scale_shape(shape: Tuple[int, int], block: Tuple[int, int]) -> Tuple[int, int]:
    """One scale per block of a weight of ``shape``; edge blocks partial."""
    return (-(-shape[0] // block[0]), -(-shape[1] // block[1]))


def quant_block(cfg: Dict) -> Optional[Tuple[int, int]]:
    """The FP8 weight block of a configuration, or None where it has no
    ``quantization_config``. Any other quantization is an error."""
    q = cfg.get("quantization_config")
    if not q:
        return None
    if q.get("quant_method") != "fp8" or q.get("fmt") != "e4m3":
        raise ValueError(f"unsupported quantization_config {q}")
    rows, cols = q["weight_block_size"]
    return int(rows), int(cols)


def linear(cfg: Dict, name: str, shape: Tuple[int, int]) -> List[Tensor]:
    """A linear layer's weight in ``weight_dtype``; under FP8 quantization,
    ``F8_E4M3`` and its F32 ``<name>_scale_inv``."""
    block = quant_block(cfg)
    if block is None:
        return [Tensor(name, shape, cfg.get("weight_dtype", "BF16"))]
    return [Tensor(name, shape, "F8_E4M3"),
            Tensor(name + "_scale_inv", scale_shape(shape, block), "F32",
                   scale_of=name, block=block)]


def attention(cfg: Dict, p: str) -> List[Tensor]:
    """Grouped-query attention: q/k/v (with biases under ``attention_bias``)
    and o projections."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or d // heads
    q, kv = heads * head_dim, cfg["num_key_value_heads"] * head_dim
    w = cfg.get("weight_dtype", "BF16")
    out: List[Tensor] = []
    for proj, rows in (("q_proj", q), ("k_proj", kv), ("v_proj", kv)):
        out += linear(cfg, p + f"self_attn.{proj}.weight", (rows, d))
        if cfg.get("attention_bias", False):
            out.append(Tensor(p + f"self_attn.{proj}.bias", (rows,), w))
    return out + linear(cfg, p + "self_attn.o_proj.weight", (d, q))


def norms(cfg: Dict, p: str) -> List[Tensor]:
    """The two RMSNorm weights of a decoder layer."""
    d, norm = cfg["hidden_size"], cfg.get("norm_dtype", "BF16")
    return [Tensor(p + "input_layernorm.weight", (d,), norm),
            Tensor(p + "post_attention_layernorm.weight", (d,), norm)]


def layer_tensors(cfg: Dict) -> List[Tensor]:
    """Every tensor of one upload, in Hugging Face naming and order."""
    model_type = cfg.get("model_type") or (
        "mixtral" if cfg.get("num_local_experts") else "qwen2")
    return registry.layout(model_type)(cfg)
