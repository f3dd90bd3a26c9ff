"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: ``bench/workloads/<cell>.json``
names its configuration (``bench/configs/<config>.json``, with the plain
reference ``<config>.py`` beside it), its traffic
(``bench/traffic/<traffic>.json``, which names the driver
``bench/drivers/<driver>.py``) and the limits of its correctness check.
Per-layer metrics are the readers ``bench/layer_metrics/<metric>.py`` that
``BENCHMARK.json`` lists.  With ``--trace 0`` the result carries the
end-to-end metrics, with ``--trace 1`` the per-layer ones, read from a
profiler trace of the window's first steps.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  The last line of standard output is one
JSON object; the numbers compared for ``correct`` end standard error.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_checkout_paths(root: str = ROOT) -> None:
    """Put the checkout (for ``bench``) and its ``src`` on the path."""
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)

NO_DEVICE = 3            # exit code: no TPU, or too few chips
BAD_CELL = 2             # exit code: the cell's files are missing or wrong


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CellError(RuntimeError):
    """The cell cannot be run as its files describe it."""


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise CellError(f"missing {os.path.relpath(path, ROOT)}") from e


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise CellError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(kind: str, name: str) -> str:
    return "bench_" + kind + "_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)


def load_cell(cell: str, root: str = ROOT) -> dict:
    """Every file of ``cell``, found by name."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise CellError(f"BENCHMARK.json has no cell {cell!r}")
    wl = load_json(root, "bench", "workloads", cell + ".json")
    for key in ("config", "traffic", "chips"):
        if wl[key] != entry[key]:
            raise CellError(f"{cell}: {key} differs between BENCHMARK.json "
                            f"and its workload file")
    c = load_json(root, "bench", "configs", wl["config"] + ".json")
    traffic = load_json(root, "bench", "traffic", wl["traffic"] + ".json")
    return {
        "bench": bench, "entry": entry, "workload": wl, "config": c,
        "traffic": traffic,
        "model": load_module(os.path.join(root, "bench", "configs",
                                          wl["config"] + ".py"),
                             _modname("ref", wl["config"])),
        "driver": load_module(os.path.join(root, "bench", "drivers",
                                           traffic["driver"] + ".py"),
                              _modname("driver", traffic["driver"])),
    }


def layer_readers(cell: str, bench: dict, root: str = ROOT) -> list:
    """(name, unit, reader module) of the per-layer metrics this cell
    reports."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append((m["name"], m["unit"], load_module(
            os.path.join(root, "bench", "layer_metrics", m["name"] + ".py"),
            _modname("metric", m["name"]))))
    return out


def arch_config(c: dict):
    """The program's configuration: its registry entry with every key of
    the file that names one of its fields."""
    import dataclasses
    from repro.configs import get_config
    base = get_config(c["registry"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    kw = {}
    for key, value in c.items():
        if key not in fields:
            continue
        cur = getattr(base, key)
        if dataclasses.is_dataclass(cur) and isinstance(value, dict):
            value = dataclasses.replace(cur, **value)
        elif isinstance(value, list):
            value = tuple(value)
        kw[key] = value
    return base.with_overrides(**kw)


class Context:
    """What a driver sees: the cell's data, the seed, the window's clock,
    the trace switch, and places to leave counters."""

    def __init__(self, cell: dict, seed: int, seconds: float,
                 trace_dir: str | None, arch):
        import jax
        self.jax = jax
        self.c, self.traffic = cell["config"], cell["traffic"]
        self.workload, self.model = cell["workload"], cell["model"]
        self.seed, self.seconds, self.arch = seed, seconds, arch
        self.check_steps = self.workload["check"]["steps"]
        self.trace_steps = self.workload.get("trace_steps", 1)
        self.trace_dir = trace_dir
        self.tracing = False
        self.counters: dict = {}
        self.compiles = 0
        self.t0 = self.t1 = None
        self.microbatches = 0
        self._compiles0 = 0
        self.span_s: dict = {}
        self.step_ends: list = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.compiles += 1

    @contextlib.contextmanager
    def span(self, name: str):
        """A call into the program: named in the trace when tracing, and
        its host seconds summed while the window is open."""
        t = time.perf_counter()
        with (self.jax.profiler.TraceAnnotation(name) if self.tracing
              else contextlib.nullcontext()):
            yield
        if self.t0 is not None and self.t1 is None:
            self.span_s[name] = (self.span_s.get(name, 0.0)
                                 + time.perf_counter() - t)

    def check_layout(self, made, program_params) -> None:
        """The seed's weights must fill the program's tree leaf for leaf."""
        jax = self.jax
        from repro.models import params as P
        want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                            program_params, is_leaf=P.is_spec)
        got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), made)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise CellError("the reference's parameter tree does not match "
                            "the program's")

    def open_window(self) -> None:
        """The window's first instant: set-up ends here."""
        self._compiles0 = self.compiles
        self._log_compiles(True)
        if self.trace_dir is not None:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(self.trace_dir,
                                          profiler_options=opts)
            self.tracing = True
        self.gc_pauses: dict = {}
        gc.callbacks.append(self._on_gc)
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()

    def _on_gc(self, phase: str, info: dict) -> None:
        """Each collection in the window: (seconds, step it fell in)."""
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.setdefault(info["generation"], []).append(
                (time.perf_counter() - self._gc_t, len(self.step_ends)))

    def window_done(self, steps: int) -> bool:
        """Asked at the end of each optimizer step of the window: a traced
        run measures ``trace_steps`` steps, an untraced one whole steps
        until ``seconds`` have passed."""
        self.step_ends.append(time.perf_counter())
        if self.trace_dir is not None:
            return steps >= self.trace_steps
        return time.perf_counter() - self.t0 >= self.seconds

    def close_window(self, microbatches: int) -> None:
        """The window's last instant (the driver has synced the device)."""
        self.t1 = time.perf_counter()
        gc.callbacks.remove(self._on_gc)
        self.microbatches = microbatches
        self.counters["compiles"] = self.compiles - self._compiles0
        self._log_compiles(False)
        steps = [b - a for a, b in zip([self.t0] + self.step_ends,
                                       self.step_ends)]
        log("step s: " + " ".join(f"{x:.4f}" for x in steps))
        log("host s in spans: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(self.span_s.items())))
        log("gc in window: " + (", ".join(
            f"gen{g} {len(p)} in {sum(x for x, _ in p):.4f} s (longest "
            f"{max(p)[0]:.4f} s in step {max(p)[1]})"
            for g, p in sorted(self.gc_pauses.items())) or "none"))
        if self.tracing:
            self.jax.profiler.stop_trace()
            self.tracing = False

    def _log_compiles(self, on: bool) -> None:
        """Name what compiles inside the window on standard error."""
        logger = logging.getLogger("jax")
        if on:
            self._handler = _CompileLog()
            logger.addHandler(self._handler)
            self.jax.config.update("jax_log_compiles", True)
        else:
            self.jax.config.update("jax_log_compiles", False)
            logger.removeHandler(self._handler)
            for msg in self._handler.first:
                log(f"in window: {msg}")

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.jax.local_devices())


class _CompileLog(logging.Handler):
    """Keeps the first few compile messages JAX logs in the window."""

    def __init__(self, keep: int = 8):
        super().__init__(logging.WARNING)
        self.keep, self.first = keep, []

    def emit(self, record) -> None:
        msg = record.getMessage()
        if len(self.first) < self.keep and "ompil" in msg:
            self.first.append(msg.splitlines()[0][:200])


def model_flops_per_token(ctx, driver) -> float:
    from bench.flops import train_flops_per_token
    return train_flops_per_token(ctx.c, ctx.c["n_stages"],
                                 driver.stages_trained(ctx),
                                 ctx.traffic["seq_len"])


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             peaks_kind: str | None = None) -> dict:
    """One run of a cell; returns the result object.  Only the CPU tests
    of the harness itself drop ``require_tpu``, naming the table entry
    whose peaks stand in (``peaks_kind``)."""
    cell = load_cell(cell_name, root)
    import jax
    devs = jax.devices()
    chips = cell["workload"]["chips"]
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(NO_DEVICE)
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, flops, plain, trace_reduce
    peak = flops.peaks(peaks_kind or devs[0].device_kind)
    arch = arch_config(cell["config"])
    trace_dir = tempfile.mkdtemp(prefix="trace-") if trace else None
    ctx = Context(cell, seed, seconds, trace_dir, arch)
    driver = cell["driver"]
    out = driver.run(ctx)
    gc.collect()
    fpt = model_flops_per_token(ctx, driver)
    window = ctx.t1 - ctx.t0
    tokens_per_s = out["tokens"] / window
    e2e = {"tokens_per_s": (tokens_per_s, "tokens/s"),
           "mfu": (100.0 * tokens_per_s * fpt / (chips * peak["bf16_flops"]),
                   "%"),
           "setup_s": (ctx.t0_wall - T_START, "s")}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    if trace:
        tr = trace_reduce.load(trace_dir)
        device["busy_s"] = trace_reduce.mean_busy_s(tr)
        device["window_s"] = window
        r = types.SimpleNamespace(
            trace=tr, window_s=window, microbatches=ctx.microbatches,
            tokens=out["tokens"], counters=ctx.counters, peak=peak,
            chips=chips, flops_per_token=fpt,
            mb_tokens=ctx.traffic["microbatch"] * ctx.traffic["seq_len"],
            memory_peak_bytes=out["memory_peak_bytes"], traffic=ctx.traffic,
            config=ctx.c)
        for name, unit, reader in layer_readers(cell_name, cell["bench"],
                                                root):
            value = reader.read(r)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell["bench"]["end_to_end"]:
            if "workloads" in m and cell_name not in m["workloads"]:
                continue
            value, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}
    log(f"window {window:.3f} s, {out['tokens']} tokens; set-up "
        f"{e2e['setup_s'][0]:.3f} s; peak {out['memory_peak_bytes']} B")
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = driver.reference(ctx, plain.Numerics("f32"))
    log(f"reference {time.perf_counter() - t_ref:.3f} s")
    correct, checks = check.decide(check.numbers(out["program"], ref),
                                   cell["workload"]["check"]["limits"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    use_checkout_paths()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return BAD_CELL
    except SystemExit as e:
        if e.code == NO_DEVICE:
            print("bench: no TPU, or fewer chips than the cell asks for",
                  file=sys.stderr)
        return int(e.code or NO_DEVICE)
    for name, ck in result["checks"].items():
        print(f"check {name} = {ck['value']!r} limit {ck['limit']!r}",
              file=sys.stderr)
    print(f"check correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
