"""Time a request waits to be admitted: ``submit`` to the first ``admit``
of the same ``uid`` after it, 95th percentile (linear between order
statistics) over the requests submitted inside the window."""
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "queue_wait_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "ttft_p95_ms"}]


def _p95(values):
    v = sorted(values)
    k = 0.95 * (len(v) - 1)
    i = int(k)
    return v[i] + (k - i) * (v[min(i + 1, len(v) - 1)] - v[i])


def read(ctx):
    events = ps.window_events(ctx)
    if events is None:
        return None
    lo, hi = ctx["window"]
    admits = {}
    for e in events:
        if e["ph"] == "i" and e["name"] == "admit":
            admits.setdefault(e["args"]["uid"], []).append(e["t0"])
    waits, unadmitted = [], 0
    for e in events:
        if e["ph"] == "i" and e["name"] == "submit" and lo <= e["t0"] < hi:
            after = [t for t in admits.get(e["args"]["uid"], ())
                     if t >= e["t0"]]
            if after:
                waits.append(min(after) - e["t0"])
            else:
                unadmitted += 1
    if not waits:
        return None
    print(f"chipbench: queue wait over {len(waits)} requests submitted in "
          f"the window ({unadmitted} more never admitted in the ring): "
          f"median {sorted(waits)[len(waits) // 2] * 1e3:.3f} ms",
          flush=True)
    return _p95(waits) * 1e3
