"""One traced run of a benchmark cell, then — same process — the device's
busy seconds by the program's own scopes.

    python3 benchmarks/scope_trace.py --workload <cell> --seed <n> [--seconds 51] [--out chiprun_out/scope_trace]

Runs ``chipbench/run.py --trace 1 --keep-trace DIR`` as the benchmark would
(the cell's own driver, traffic and sizing; nothing of the benchmark is
edited), then reads the kept profile with
``deepspeed_tpu.telemetry.device_scopes.by_scope`` against the scope tables
of the engine that ran (``telemetry.trace.kept("programs").tables()``), and
beside it ``idle_gaps`` on the same loaded profile: where the device was
busy, by layer; where it was not, by what the host was doing — one clock, one
window.  Writes ``<out>/<cell>/tables.json`` (the tables, for ``python -m
deepspeed_tpu.telemetry.device_scopes DIR --tables``), ``by_scope.json`` and
``by_scope.txt``; the profile itself stays on the machine (too big to bring
back).  Prints what the tables cost to build and the reader to run.  On the
chip through ``chiprun``; ``--rehearse`` runs the same code at tiny widths on
``JAX_PLATFORMS=cpu`` (paths only: a CPU profile has no device plane, so the
reader is skipped).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE_METRICS = ("sample_vocab_passes", "scope_cover.serve",
                 "scope_cover.train")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "scope_trace"))
    ap.add_argument("--profile", default=None,
                    help="where the raw profile is kept (default: a "
                         "directory of the checkout that is not brought back)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import run as cb_run

    # a cell whose own test pins its list of per-layer metrics (PERF.md
    # section 7) is not in the scope metrics' lists: here it reports them
    # all the same, the entries appended to what ``load_cell`` returns
    load_cell = cb_run.load_cell

    def with_scope_metrics(name, *more, **kw):
        spec = load_cell(name, *more, **kw)
        bench = cb_run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
        reports = {m["name"] for m in spec["end_to_end"]}
        have = {m["name"] for m in spec["per_layer"]}
        spec["per_layer"] += [
            m for m in bench["per_layer"]
            if m["name"] in SCOPE_METRICS and m["name"] not in have
            and m["moves"] in reports]
        return spec

    cb_run.load_cell = with_scope_metrics
    out = os.path.join(args.out, args.workload)
    os.makedirs(out, exist_ok=True)
    profile = args.profile or os.path.join(ROOT, "_scratch", "profiles",
                                           args.workload)
    cell = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "1",
            "--keep-trace", profile]
    rc = cb_run.main(cell + (["--rehearse"] if args.rehearse else []))
    if rc:
        return rc

    from deepspeed_tpu.telemetry import device_scopes, idle_gaps, trace
    from deepspeed_tpu.telemetry import profile as prof

    programs = trace.kept("programs")
    if programs is None:
        print("scope_trace: no engine recorded a program", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    tables = programs.tables()
    built = time.perf_counter() - t0
    with open(os.path.join(out, "tables.json"), "w") as f:
        json.dump(tables, f)
    print("scope_trace: tables of " + ", ".join(
        f"{name} ({len(t['instructions'])} instructions, "
        f"{t['build_s']:.2f} s, {t['backend_compiles']} compiles)"
        for name, t in tables.items()) + f"; this call {built:.2f} s")
    if args.rehearse:
        return 0
    t0 = time.perf_counter()
    loaded = prof.load(profile)
    t_load = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = device_scopes.by_scope(loaded, tables)
    t_read = time.perf_counter() - t0
    text = device_scopes.render(result)
    with open(os.path.join(out, "by_scope.json"), "w") as f:
        json.dump(result, f)
    idle = idle_gaps.idle_by_span(loaded.ops, loaded.spans)
    text += (f"\nidle {idle['idle_s']:.4f} s of {idle['window_s']:.3f}: "
             + ", ".join(f"{n} {s * 1e3:.1f} ms"
                         for n, s, _ in idle["by_span"][:6]))
    text += (f"\nscope_trace: {len(loaded.ops):,} device operations; the "
             f"profile loaded in {t_load:.1f} s, read by scope in "
             f"{t_read:.1f} s")
    with open(os.path.join(out, "by_scope.txt"), "w") as f:
        f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
