#!/usr/bin/env python3
"""The benchmark's one command: run one cell, print one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process IS the sidecar process.  It starts the cell's load
generator (a child that holds no device) before it touches JAX, boots
``hotstuff_tpu.sidecar.service.serve()`` in a thread of its own process —
the function ``python -m hotstuff_tpu.sidecar`` calls, with the
configuration's arguments — and lets the child reach the served path over
the socket.  So the process that holds the chip can profile it, read its
memory and count its compilations itself: no hook, no second JAX process.

It knows no cell by name.  ``BENCHMARK.json`` names the cell's
configuration (``configs/<name>.json``) and traffic mix
(``traffic/<mix>.json``); the mix names its driver
(``drivers/<driver>.py``); a traced run reads each per-layer metric of
the cell through the reader its layer file names
(``layers/<metric>.json`` -> ``readers/<kind>.py``).

Without a TPU, or with fewer chips than the cell asks for, everything
still runs (the CPU rehearsal, ``benchmark/README.md``) and then the run
is refused: exit code 1, no result line, no rate or latency printed.
"""

from __future__ import annotations

import time

T0 = time.monotonic()          # set-up is counted from here

import argparse                # noqa: E402
import glob                    # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import logging                 # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import socket                  # noqa: E402
import sys                     # noqa: E402
import threading               # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (BENCH, REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from yardstick import arith, trace_reduce  # noqa: E402

# Seconds of the window that a traced run profiles, and how far into the
# window the slice starts (both cut to fit a short window).  One second
# of qc100.solo is ~23 launches and ~1.2 million device events; stopping
# the profiler and reading them back took ~105 s for a 2 s slice on the
# chip (PR 24), and a run has 360 s.
PROFILE_SLICE_S = 1.0
PROFILE_AFTER_S = 2.0
# A cold boot compiles every warmed shape: the contract gives a cell's
# first run 1,200 s.
BOOT_LIMIT_S = 1100.0


class Refused(RuntimeError):
    """The run cannot give a result; the message says why."""


def say(msg: str):
    print(f"bench: {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``drivers/<name>.py`` or ``readers/<name>.py``, found by name."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(manifest_path: str, workload: str) -> dict:
    """The cell with everything that belongs to it, from data alone."""
    manifest = load_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in {manifest_path} "
                      f"(it has: {', '.join(sorted(cells))})")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in manifest["configs"]}
    cell["config_path"] = os.path.join(root, configs[cell["config"]]["file"])
    # A mix sits beside its configuration's directory (the rehearsal has
    # small ones of its own), else with the benchmark's.
    beside = os.path.dirname(os.path.dirname(cell["config_path"]))
    mixes = [os.path.join(d, "traffic", f"{cell['traffic']}.json")
             for d in (beside, BENCH)]
    cell["mix_path"] = next((m for m in mixes if os.path.isfile(m)), mixes[0])
    cell["layers_dir"] = os.path.join(BENCH, "layers")
    cell["config_data"] = load_json(cell["config_path"])
    cell["mix_data"] = load_json(cell["mix_path"])
    if int(cell["config_data"]["chips"]) != int(cell["chips"]):
        raise Refused(f"cell asks for {cell['chips']} chip(s), its "
                      f"configuration for {cell['config_data']['chips']}")

    def of_cell(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if of_cell(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if of_cell(m)]
    return cell


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Sidecar:
    """``service.serve()`` in a thread of this process, as
    ``tests/test_sidecar.py``'s ``served`` fixture boots it."""

    def __init__(self, sidecar_args: dict, trace_path: str | None):
        from hotstuff_tpu.sidecar import service

        self.port = free_port()
        self._servers = servers = []
        self._error = None
        self._ready = threading.Event()

        class Recording(service.SidecarServer):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                servers.append(self)

        service.SidecarServer = Recording

        def run():
            try:
                service.serve(port=self.port, ready_event=self._ready,
                              trace_path=trace_path, **sidecar_args)
            except BaseException as e:  # noqa: BLE001 — handed to main
                self._error = e
                self._ready.set()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="sidecar-serve")
        self._thread.start()

    def wait_ready(self, timeout_s: float):
        if not self._ready.wait(timeout_s):
            raise Refused(f"the sidecar was not ready after {timeout_s:.0f}s")
        if self._error is not None:
            raise Refused(f"serve() failed: {self._error!r}")

    def stop(self):
        for srv in self._servers:
            srv.shutdown()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise Refused("serve() did not end after shutdown()")


class CompileCounter:
    """Times at which JAX compiled or loaded a program, from
    ``jax.monitoring``: one inside the window means a shape was not
    warmed, and the run is refused."""

    # Fires once for every program built, compiled anew or read back
    # from the persistent cache.
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.seen.append((time.monotonic(), duration))

    def inside(self, t_start: float, t_end: float) -> list:
        return [(t - t_start, d) for t, d in self.seen
                if t_start <= t <= t_end]


def profile_slice(profile_dir: str, t_start: float, t_end: float) -> list:
    """Profile a slice of the window with the JAX profiler; returns the
    slice's [start, stop] in wall-clock nanoseconds."""
    import jax

    seconds = t_end - t_start
    after = min(PROFILE_AFTER_S, seconds / 4)
    length = min(PROFILE_SLICE_S, seconds / 2)
    time.sleep(max(0.0, t_start + after - time.monotonic()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(profile_dir, profiler_options=options)
    start_ns = time.time_ns()
    with jax.profiler.TraceAnnotation(
            f"{trace_reduce.CLOCK_MARK}{start_ns}"):
        pass
    time.sleep(length)
    stop_ns = time.time_ns()
    jax.profiler.stop_trace()
    return [start_ns, stop_ns]


def read_spans(path: str, wall_lo: float, wall_hi: float) -> list:
    """The sidecar's spans that ended inside [wall_lo, wall_hi]."""
    spans = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    sp = json.loads(line)
                except ValueError:
                    continue
                if wall_lo <= sp.get("t", 0) <= wall_hi:
                    spans.append(sp)
    except OSError:
        pass
    return spans


def check_served_by_device(stats: dict, before: dict, config: dict,
                           chips: int) -> list:
    """Hold the OP_STATS snapshot at the window's end to "the device
    answered, and the configured route did": the violated conditions."""
    bad = []
    device = stats.get("device")
    if not isinstance(device, dict):
        bad.append("OP_STATS has no `device` section: the sidecar holds "
                   "no device")
    else:
        if device.get("platform") != "tpu":
            bad.append(f"device.platform is {device.get('platform')!r}, "
                       "not 'tpu'")
        if device.get("count") != chips:
            bad.append(f"device.count is {device.get('count')!r}, "
                       f"not {chips}")
    paths = stats.get("paths") or {}
    if paths.get("host", 0):
        bad.append(f"paths has a host entry: {paths}")
    route = config["route"]
    routed = paths.get(route, 0) - (before.get("paths") or {}).get(route, 0)
    if routed <= 0:
        bad.append(f"no launch took the configured route {route!r} in the "
                   f"window: {paths}")
    guard = stats.get("guard") or {}
    for key in ("wedges", "host_fallback_records"):
        if guard.get(key, 0):
            bad.append(f"guard.{key} is {guard.get(key)}")
    hits = (stats.get("dedup") or {}).get("cache_hits", 0) - \
        (before.get("dedup") or {}).get("cache_hits", 0)
    if hits:
        bad.append(f"{hits} verdict(s) came from the verdict cache")
    return bad


def compared_numbers(stats: dict, before: dict, config: dict, pool: dict,
                     window: dict, requests: list) -> dict:
    """Every number ``correct`` rests on, beside its limit: {name:
    {"value", "limit"}}.  All are exact counts; ``limit`` is the value a
    sound run reads (0) or, for ``route_launches``, its least (">=1")."""
    def moved(section, key):
        return (stats.get(section) or {}).get(key, 0) - \
            (before.get(section) or {}).get(key, 0)

    def of_status(*statuses):
        return sum(r["status"] in statuses for r in requests)

    guard = stats.get("guard") or {}
    counts = {
        "sample_disagreements": len(pool["sample"]["disagreements"]),
        "unmeasured_wrong": window["unmeasured_wrong"],
        "replies_wrong": of_status("mismatch"),
        "never_answered": of_status("unanswered", "error"),
        "cache_hits": moved("dedup", "cache_hits"),
        "host_path_launches": (stats.get("paths") or {}).get("host", 0),
        "wedges": guard.get("wedges", 0),
        "host_fallback_records": guard.get("host_fallback_records", 0),
    }
    out = {k: {"value": v, "limit": 0} for k, v in counts.items()}
    out["route_launches"] = {"value": moved("paths", config["route"]),
                             "limit": ">=1"}
    return out


def within(check: dict) -> bool:
    """Whether a compared number keeps to its limit."""
    if check["limit"] == ">=1":
        return check["value"] >= 1
    return check["value"] == check["limit"]


def end_to_end_values(requests, t_start, t_end, setup_s) -> dict:
    lat = arith.latencies_ms(requests, t_start, t_end)
    out = {"setup_s": setup_s,
           "verify_sigs_per_s": arith.sigs_per_s(requests, t_start, t_end)}
    if lat:
        out["verify_p50_ms"] = arith.percentile(lat, 50)
        out["verify_p95_ms"] = arith.percentile(lat, 95)
    return out


def per_layer_values(cell: dict, run: dict, notes: dict) -> dict:
    out = {}
    for metric in cell["per_layer"]:
        name = metric["name"]
        layer = load_json(os.path.join(cell["layers_dir"], f"{name}.json"))
        reader = load_module("readers", layer["reader"]["kind"])
        try:
            value = reader.read(layer["reader"], run)
        except Exception as e:  # noqa: BLE001 — one reader, one metric
            notes.setdefault("reader_errors", {})[name] = repr(e)
            value = None
        if value is not None:
            out[name] = value
    return out


def chips_found(devices, chips: int) -> bool:
    """The harness's look for a chip."""
    return devices[0].platform == "tpu" and len(devices) >= chips


def memory_peak_bytes(devices):
    """The peak on the fullest chip; None where the backend reports none."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max((p for p in peaks if p), default=None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"),
                    help="another list of cells than BENCHMARK.json "
                         "(the CPU rehearsal's)")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refused as e:
        say(f"REFUSED, no result: {e}")
        return 1


def run(args) -> int:
    cell = resolve_cell(args.manifest, args.workload)
    config, mix = cell["config_data"], cell["mix_data"]
    chips = int(cell["chips"])
    work = os.path.join(BENCH, ".work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    logging.basicConfig(
        filename=os.path.join(work, "sidecar.log"), level=logging.INFO,
        format="%(asctime)s.%(msecs)03dZ %(levelname)s [%(name)s] "
               "%(message)s", datefmt="%Y-%m-%dT%H:%M:%S")
    notes = {"cell": cell["name"], "seed": args.seed,
             "seconds": args.seconds, "trace": args.trace}

    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # nothing outside its checkout.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(work, "tpu_logs"))

    # 1. The load generator first: it holds no device, and builds its
    #    pool while the sidecar warms up.
    driver = load_module("drivers", mix["driver"])
    handle = driver.start(cell, args.seed, work)
    sidecar = None
    try:
        # 2. Now JAX: this process takes the chip(s).
        import jax

        counter = CompileCounter()
        devices = jax.devices()
        platform_ok = chips_found(devices, chips)
        if not platform_ok:
            say(f"no TPU with {chips} chip(s) here "
                f"({devices[0].platform} x{len(devices)}): rehearsal only, "
                "the run will be refused at its end")
        spans_path = os.path.join(work, "spans.jsonl") if args.trace else None
        t_boot = time.monotonic()
        sidecar = Sidecar(dict(config["sidecar"]), spans_path)
        sidecar.wait_ready(BOOT_LIMIT_S)
        t_ready = time.monotonic()
        pool = handle.expect("pool", timeout_s=600)
        t_pool = time.monotonic()
        notes["pool"] = {k: v for k, v in pool.items() if k != "event"}
        built = counter.inside(t_boot, t_ready)
        notes["boot_programs"] = {"built": len(built),
                                  "backend_seconds": sum(d for _, d in built)}
        notes["setup_split_s"] = {
            "imports_and_start": t_boot - T0,
            "sidecar_boot": t_ready - t_boot,
            "waited_for_pool_after_boot": t_pool - t_ready,
            "pool_build_in_child": pool["pool_s"],
            "sample_check_in_child": pool["sample_s"]}
        if pool["sample"]["disagreements"]:
            raise Refused("the generator's ground truth disagrees with the "
                          f"plain reference at {pool['sample']}")

        # 3. Unmeasured requests, then the window.
        handle.go(sidecar.port, args.seconds)
        window = handle.expect("window", timeout_s=300)
        t_start, t_end = window["t_start"], window["t_end"]
        setup_s = t_start - T0
        notes["setup_split_s"]["connect_and_unmeasured"] = t_start - t_pool
        wall_slice = None
        profile_dir = os.path.join(work, "profile")
        if args.trace:
            wall_slice = profile_slice(profile_dir, t_start, t_end)
        result = handle.expect(
            "result", timeout_s=args.seconds + mix.get("drain_s", 5) + 120)
        t_done = time.monotonic()
    finally:
        handle.stop()
        if sidecar is not None:
            sidecar.stop()

    # 4. From records to numbers.
    requests = result["requests"]
    stats, before = result["stats_end"], result["stats_start"]
    attempted, failed = arith.attempted_failed(requests, t_start, t_end)
    by_status: dict = {}
    by_kind: dict = {}
    for r in requests:
        by_status[r["status"]] = by_status.get(r["status"], 0) + 1
        by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
    notes.update(by_status=by_status, by_kind=by_kind,
                 paths=stats.get("paths"), paths_before=before.get("paths"),
                 dedup=stats.get("dedup"), compile=stats.get("compile"),
                 guard=stats.get("guard"),
                 unmeasured=window["unmeasured"],
                 undrained=result["undrained_connections"])
    problems = check_served_by_device(stats, before, config, chips)
    checks = compared_numbers(stats, before, config, pool, window, requests)
    # The replies' own counts; OP_STATS' are worded above.
    problems += [f"{name} is {checks[name]['value']}, not 0"
                 for name in ("unmeasured_wrong", "replies_wrong",
                              "never_answered") if not within(checks[name])]
    compiled = counter.inside(t_start, t_done)
    notes["compiles_in_window"] = compiled
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": memory_peak_bytes(devices[:chips])}

    line = {"correct": not problems, "attempted": attempted,
            "failed": failed, "metrics": {}, "device": device}
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    if args.trace:
        wall_lo = window["t_wall"]
        spans = read_spans(spans_path, wall_lo,
                           wall_lo + (t_end - t_start))
        notes["spans_in_window"] = len(spans)
        profile = None
        try:
            xplane = glob.glob(os.path.join(
                profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not xplane:
                raise ValueError(f"no .xplane.pb under {profile_dir}")
            trace = trace_reduce.load_xplane(
                xplane[0], keep=lambda name: name.startswith(
                    ("/device:TPU:", "/host:CPU")))
            profile = trace_reduce.reduce_trace(
                trace, spans, wall_slice, config["program_pattern"])
        except Exception as e:  # noqa: BLE001 — reported, metrics left out
            notes["profile_error"] = repr(e)
        notes["profile"] = profile
        values = per_layer_values(
            cell, {"stats": stats, "spans": spans, "profile": profile,
                   "config": config, "mix": mix, "device": device}, notes)
        names = [m["name"] for m in cell["per_layer"]]
        if profile:
            device["busy_s"] = profile["busy_s"]
            device["window_s"] = profile["window_s"]
            line["breakdown"] = {"device_ops": profile["device_ops"],
                                 "idle_gaps": profile["idle_gaps"]}
    else:
        values = end_to_end_values(requests, t_start, t_end, setup_s)
        names = [m["name"] for m in cell["end_to_end"]]
    for name in names:
        if name in values:
            line["metrics"][name] = {"value": values[name],
                                     "unit": units[name]}
    notes["problems"] = problems
    with open(os.path.join(work, "notes.json"), "w", encoding="utf-8") as f:
        json.dump(notes, f, indent=1, sort_keys=True, default=str)
    say("notes: " + json.dumps(
        {k: notes[k] for k in ("setup_split_s", "by_status", "by_kind",
                               "paths", "compiles_in_window", "problems")},
        sort_keys=True, default=str))

    # 5. The gates: a run that did not measure the chip prints nothing.
    if not platform_ok:
        raise Refused(
            f"platform is {devices[0].platform!r} with {len(devices)} "
            f"device(s), the cell needs a TPU with {chips}; everything else "
            f"ran, problems besides the platform: "
            f"{[p for p in problems if 'device.' not in p] or 'none'}")
    if compiled:
        raise Refused(f"JAX compiled or loaded a program inside the window "
                      f"(a shape was not warmed): {compiled[:4]}")
    if device["memory_peak_bytes"] is None:
        raise Refused("the device reports no memory_stats()")
    if args.trace and "busy_s" not in device:
        raise Refused("the traced run gave no device busy time: "
                      f"{notes.get('profile_error')}")
    if not line["metrics"]:
        raise Refused("no metric could be read")
    # What `correct` compared, each number beside its limit: the last
    # key of the line and the last lines on standard error.
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
