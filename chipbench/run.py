"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m chipbench.run ...            # the same

One process per run, no children: it loads, warms up the cell's own shapes,
measures for ``--seconds`` and prints ONE JSON object as the last line of
its standard output (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and ``breakdown`` in a traced run).  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

Everything that belongs to one cell is data, found by the names in
``BENCHMARK.json`` (see ``chipbench/README.md``):

    configs/<config>.json       the model configuration as it is run
    traffic/<traffic>.json      the traffic mix; its ``kind`` names a driver
    workloads/<cell>.json       the engine's sizing for this cell
    drivers/<kind>.py           one traffic driver per kind
    families/<family>.py        all that knows a family: the program's model,
                                its sizes and counts, its plain reference
    layer_metrics/*.py          one small reader per per-layer metric

Without a TPU (or with fewer chips than the cell asks for) it exits with a
non-zero code and prints no result — unless ``--rehearse`` is given, which
runs the same code at the tiny widths of each file's ``rehearse`` block on
``JAX_PLATFORMS=cpu`` and names ``cpu`` in ``device``: a rehearsal checks
paths and control flow and is never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # run as a script: make the repo importable
    sys.path.insert(0, ROOT)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile"


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _rehearsed(data: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """A data file with its ``rehearse`` block applied (or dropped)."""
    data = dict(data)
    tiny = data.pop("rehearse", {})
    return {**data, **tiny} if rehearse else data


def load_cell(name: str, rehearse: bool = False, root: str = ROOT
              ) -> Dict[str, Any]:
    """Everything ``BENCHMARK.json`` and the data files say about a cell."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = os.path.join(root, "chipbench")

    def reports(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "cell": cell,
        "config": _rehearsed(_load_json(os.path.join(root, entry["file"])),
                             rehearse),
        "traffic": _rehearsed(_load_json(os.path.join(
            here, "traffic", cell["traffic"] + ".json")), rehearse),
        "sizing": _rehearsed(_load_json(os.path.join(
            here, "workloads", name + ".json")), rehearse),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def layer_metric_readers(root: str = ROOT) -> Dict[str, Callable]:
    """metric name -> ``read(ctx)``, from every file of
    ``chipbench/layer_metrics``: each holds ``SPECS`` (a list of dicts with
    at least ``name``) and one ``read``."""
    readers: Dict[str, Callable] = {}
    folder = os.path.join(root, "chipbench", "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "chipbench.layer_metrics." + fname[:-3].replace(".", "_"),
            os.path.join(folder, fname))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        for s in module.SPECS:
            if s["name"] in readers:
                raise ValueError(f"two readers for metric {s['name']!r}")
            readers[s["name"]] = module.read
    return readers


class Job:
    """What a driver is handed."""

    def __init__(self, args, spec: Dict[str, Any]):
        from chipbench import families
        from chipbench.spans import Spans, TraceWindow

        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.sizing = spec["sizing"]
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearse = bool(args.rehearse)
        self.spans = Spans()
        self.tracer = TraceWindow(bool(args.trace), self.seconds,
                                  self.rehearse, args.keep_trace)
        self.family = families.load(self.config)
        self.setup_s: Optional[float] = None
        self._compiles = [0]
        self.notes: List[str] = []

    def note(self, text: str) -> None:
        """An earlier line of standard output (never the last)."""
        self.notes.append(text)
        print("chipbench: " + text, flush=True)

    def window_opened(self, t_open: float) -> None:
        self.setup_s = t_open - T_PROCESS

    def compiles(self) -> int:
        """Backend compilations in this process so far."""
        return self._compiles[0]

    def count_compiles(self) -> None:
        import jax.monitoring

        box = self._compiles

        def on_duration(event, duration, **kwargs):
            if event.startswith(_BACKEND_COMPILE):
                box[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def _memory_peak(devices) -> int:
    """Peak device memory of the fullest chip, from ``memory_stats()``.
    The TPU runtime keeps a loaded program's temporaries in space it
    RESERVES outside ``bytes_in_use`` (a train step with 4.5 GB of state and
    9.6 GB of temporaries reads ``peak_bytes_in_use`` 4.5 GB), so the peak
    is the larger of ``peak_bytes_in_use`` (set-up transients included) and
    what is held at the end: ``bytes_in_use + bytes_reserved``."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        held = int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)), held)
    return peak


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on JAX_PLATFORMS=cpu: paths and "
                         "control flow only, never a measurement")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: keep the raw profiler trace "
                         "there (for looking at one by hand)")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload, args.rehearse)

    import jax

    devices = jax.devices()
    platform, chips = devices[0].platform, int(spec["cell"]["chips"])
    if args.rehearse:
        if platform != "cpu":
            print("chipbench: --rehearse is for JAX_PLATFORMS=cpu, JAX "
                  f"found {platform!r}", file=sys.stderr)
            return 2
    elif platform != "tpu" or len(devices) != chips:
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} device(s) of platform {platform!r} — "
              "refusing to run", file=sys.stderr)
        return 2

    from deepspeed_tpu.utils.platform import enable_compile_cache

    from chipbench import peaks

    cache_dir = enable_compile_cache(ROOT)
    job = Job(args, spec)
    job.count_compiles()
    if not args.rehearse:
        peaks.peaks_for(devices[0].device_kind)   # unknown chip: an error
    job.note(f"{args.workload} seed {args.seed} on {platform} "
             f"{devices[0].device_kind} x{len(devices)}; compile cache "
             f"{cache_dir}")

    driver = importlib.import_module(
        "chipbench.drivers." + spec["traffic"]["kind"])
    out = driver.run(job)
    used = out["devices"]
    device = {**peaks.device_info(used),
              "memory_peak_bytes": _memory_peak(used)}
    job.note("memory_stats of the first chip: "
             + json.dumps(used[0].memory_stats() or {}))
    job.note("set-up spans (s): " + json.dumps({
        k: round(sum(v), 3) for k, v in job.spans.durations.items()
        if k.startswith("cb.setup.")}) + f"; compiles in set-up and "
        f"window {job.compiles()}")

    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    values: Dict[str, float] = {}
    result: Dict[str, Any] = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"]}
    if args.trace:
        ctx = {**out, "cell": spec["cell"], "config": spec["config"],
               "traffic": spec["traffic"], "sizing": spec["sizing"],
               "spans": job.spans, "trace": job.tracer.reduced,
               "device": device, "rehearse": args.rehearse,
               "peaks": None if args.rehearse
               else peaks.peaks_for(device["kind"])}
        readers = layer_metric_readers()
        for m in spec["per_layer"]:
            value = readers[m["name"]](ctx) if m["name"] in readers else None
            if value is not None:
                values[m["name"]] = float(value)
        red = job.tracer.reduced
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        values = {"setup_s": job.setup_s, **out["end_to_end"]}
        values = {k: v for k, v in values.items() if k in units}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = device
    job.note("detail " + json.dumps({"counters": out["counters"],
                                     "window_s": out["window_s"],
                                     "setup_s": job.setup_s}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
