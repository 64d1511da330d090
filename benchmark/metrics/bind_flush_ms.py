"""The bind flush: per cycle that flushed, from the first ``bind_flush.*``
async span's start to the last one's end; the mean over those cycles."""
from volcano_tpu.trace import tracer


def read(ctx):
    lengths = []
    for rec in ctx.records:
        ev = [e for e in tracer.chrome_trace(rec)["traceEvents"]
              if e["tid"] == 2 and e["name"].startswith("bind_flush.")]
        if ev:
            lengths.append((max(e["ts"] + e["dur"] for e in ev) -
                            min(e["ts"] for e in ev)) / 1000.0)
    return sum(lengths) / len(lengths) if lengths else None
