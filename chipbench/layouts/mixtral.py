"""Mixtral's decoder layer: grouped-query attention, a router and
``num_local_experts`` gated experts (``w1``, ``w2``, ``w3``), two RMSNorms."""

from bench.spec import Tensor, attention, linear, norms


def layer_tensors(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_exp = cfg["num_local_experts"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        m = p + "block_sparse_moe."
        out += attention(cfg, p)
        out.append(Tensor(m + "gate.weight", (n_exp, d),
                          cfg.get("weight_dtype", "BF16")))
        for e in range(n_exp):
            for w, shape in (("w1", (f, d)), ("w2", (d, f)), ("w3", (f, d))):
                out += linear(cfg, m + f"experts.{e}.{w}.weight", shape)
        out += norms(cfg, p)
    return out
