"""Share of the user bytes taken in during the window that the decision
stage resolved by tensor or file dedup, from /stats store counters."""

from bench.readers import delta


def read(run):
    raw = delta(run, "store", "raw_bytes")
    if raw <= 0:
        return None
    files = sum(r["bytes"] for r in run.records
                if r["status"] == 200 and r["row"].get("file_dedup_hit"))
    return 100.0 * (delta(run, "store", "codec_bytes", "dedup") + files) / raw
