"""Paper Figure 10: per-model data reduction distribution for BitX vs ZipNN vs
zstd (violin-plot summary statistics: quartiles + mean)."""

from __future__ import annotations

import numpy as np
import zstandard as zstd

from benchmarks.common import Ctx, emit
from repro.core.bitx import BitXCodec
from repro.formats.safetensors import SafetensorsFile


def _per_model_ratios(ctx: Ctx):
    codec = BitXCodec()
    zc = zstd.ZstdCompressor(level=3)
    # base file per family, by generator ground truth (ctx.families)
    base_files = {}
    for rid, kind in ctx.manifest:
        if kind == "base":
            base_files[ctx.families[rid]] = ctx.primary_file(rid)

    ratios = {"bitx": [], "zipnn": [], "zstd": []}
    for rid, kind in ctx.manifest:
        if kind not in ("finetune", "checkpoint", "vocab_expanded"):
            continue
        fam = ctx.families.get(rid)
        if fam not in base_files:
            continue
        raw = comp_bitx = comp_zipnn = comp_zstd = 0
        with SafetensorsFile(ctx.primary_file(rid)) as sf, \
             SafetensorsFile(base_files[fam]) as bf:
            base_by_name = {ti.name: ti for ti in bf.infos}
            for ti in sf.infos:
                arr = sf.tensor(ti.name)
                raw += ti.nbytes
                comp_zstd += len(zc.compress(arr.tobytes()))
                frames, _ = codec.encode_planes(arr)
                comp_zipnn += sum(len(f) for f in frames)
                bt = base_by_name.get(ti.name)
                if bt is not None and bt.shape == ti.shape and bt.dtype_str == ti.dtype_str:
                    fr, _ = codec.encode_delta(bf.tensor(ti.name).reshape(-1),
                                               arr.reshape(-1))
                    comp_bitx += sum(len(f) for f in fr)
                else:
                    comp_bitx += sum(len(f) for f in frames)  # zipnn fallback
        ratios["bitx"].append(1 - comp_bitx / raw)
        ratios["zipnn"].append(1 - comp_zipnn / raw)
        ratios["zstd"].append(1 - comp_zstd / raw)
    return ratios


def run(ctx: Ctx) -> dict:
    ratios = _per_model_ratios(ctx)
    out = {}
    for method, vals in ratios.items():
        v = np.asarray(vals)
        out[method] = {
            "n_models": len(vals),
            "mean": round(float(v.mean()), 4),
            "p25": round(float(np.percentile(v, 25)), 4),
            "median": round(float(np.median(v)), 4),
            "p75": round(float(np.percentile(v, 75)), 4),
            "max": round(float(v.max()), 4),
        }
    out["bitx_beats_zipnn"] = out["bitx"]["median"] > out["zipnn"]["median"]
    out["zipnn_beats_zstd"] = out["zipnn"]["median"] > out["zstd"]["median"]
    out["bitx_over_50pct_fraction"] = round(
        float((np.asarray(ratios["bitx"]) > 0.5).mean()), 4)
    return out


if __name__ == "__main__":
    from benchmarks.common import build_ctx
    emit("compression_methods", run(build_ctx()))
