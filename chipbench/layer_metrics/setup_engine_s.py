"""Self time of the engine's construction: ``init_serving`` (serving) or
``initialize`` (training), all of it, less the programs' ``build`` spans
and less every function JAX built inside it (``_setup_spans.py``).  Also
prints the whole account of the run's ``setup_s`` — the rows are disjoint
and sum to it — with what is left of each ``cb.setup.*`` span, what the
benchmark's own jits took, and the seconds NO span covers."""
from chipbench.layer_metrics import _setup_spans as ss

SPECS = [{"name": "setup_engine_s.serve", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "setup_s"},
         {"name": "setup_engine_s.train", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "engine",
          "moves": "setup_s"}]


def read(ctx):
    got = ss.account(ctx)
    if got is None:
        return None
    detail = {}
    for (row, name), seconds in sorted(got["by"].items(),
                                       key=lambda kv: -kv[1]):
        if row in ("cb_left_s", "ring_other_s", "bench_jit_s",
                   "uncovered_s"):
            detail.setdefault(row, []).append(f"{name} {seconds:.3f}")
    print(f"chipbench: set-up account (s) of setup_s {got['setup_s']:.3f}: "
          + "; ".join(
              f"{row} {got[row]:.3f}" + (
                  " (" + ", ".join(detail[row]) + ")" if row in detail
                  else "") for row in ss.ROWS), flush=True)
    return ss.row(ctx, "engine_s", got)
