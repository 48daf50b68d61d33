"""Qwen2's decoder layer, and any Llama-style dense one: grouped-query
attention, a gated MLP, two RMSNorms."""

from bench.spec import attention, linear, norms


def layer_tensors(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += attention(cfg, p)
        for proj, shape in (("gate_proj", (f, d)), ("up_proj", (f, d)),
                            ("down_proj", (d, f))):
            out += linear(cfg, p + f"mlp.{proj}.weight", shape)
        out += norms(cfg, p)
    return out
