"""Host time a scheduler step spends PLANNING its calls: ``plan_s`` of its
``step.prefill`` + ``step.decode`` spans (choosing rows and groups,
reserving blocks — the KV manager's calls are inside it — and the numpy
packing of ids / tables / bases), mean over the window's steps.  Also
prints how much of the two phases' self time the three segments cover."""
from chipbench.layer_metrics import _host_segments as hs
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "host_plan_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    got = hs.window_phases(ctx)
    if got is None:
        return None
    steps, phases, flights = got
    own = sum(ps.self_s(p, flights) for p in phases)
    by = {k: sum(p["args"].get(k, 0.0) for p in phases) for k in hs.SEGMENTS}
    print("chipbench: host segments per step, ms (mean): " + "; ".join(
        f"{k} {1e3 * v / len(steps):.3f}" for k, v in by.items())
        + f"; they cover {100 * sum(by.values()) / own:.2f} % of the self "
        f"time of step.prefill + step.decode ({1e3 * own / len(steps):.3f} "
        f"ms a step) over {len(steps)} steps", flush=True)
    return 1e3 * by["plan_s"] / len(steps)
