"""The benchmark's one entry point.

    python3 -m benchmarks.run --workload W --seed N --seconds S --trace 0|1

Everything that belongs to one configuration, one traffic mix, one job
kind or one per-layer metric is a file found by the name
``BENCHMARK.json`` gives (see ``benchmarks/README.md``); this file holds
none of it. The last line of standard output is the result; earlier
lines are notes (JSON objects with a ``note`` key).

``--rehearsal`` (never passed by the driver) runs the same control flow
at tiny widths on the CPU, on as many virtual devices as the cell has
chips. Its result names the CPU and is never a measurement. Without it
and without a TPU the run fails at once.
"""

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_reader(name):
    """The per-layer metric's reader: ``layer_metrics/<name>.py``, a
    module with ``read(ctx) -> number or None``. Names hold dots, so the
    file is loaded by its path."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# what every ``families/<family>.py`` exports (``README.md`` says what
# each must do): the program's side of the architecture, then its
# yardstick. No name has a default
FAMILY_CONTRACT = (
    "REHEARSAL_FIELDS", "program_config", "init_params", "logical_axes",
    "loss_fn", "reference", "param_count", "train_flops_per_token",
    "flash_attention_flops")


class FamilyContractError(Exception):
    """A family module lacks a name of ``FAMILY_CONTRACT``."""


def load_family(name):
    """``families/<name>.py``, refused where it lacks a name of the
    contract: a yardstick that is not there is never guessed."""
    family = importlib.import_module("benchmarks.families." + name)
    missing = [n for n in FAMILY_CONTRACT if not hasattr(family, n)]
    if missing:
        raise FamilyContractError(
            f"benchmarks/families/{name}.py lacks {', '.join(missing)}: a "
            f"family exports {', '.join(FAMILY_CONTRACT)}")
    return family


def metrics_of(cell_name, entries):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def note(kind, **fields):
    print(json.dumps({"note": kind, **fields}), flush=True)


def no_chip(device, cell, args):
    """Exit code 3 and why, on standard error, where JAX found no TPU (and
    this is no rehearsal) or fewer chips than the cell asks for; else 0."""
    if device["platform"] != "tpu" and not args.rehearsal:
        print(f"no TPU: JAX found {device['platform']!r}. A measurement "
              "needs the chip; --rehearsal runs the control flow on the "
              "CPU", file=sys.stderr)
        return 3
    if device["found"] < cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} chips, JAX found "
              f"{device['found']}", file=sys.stderr)
        return 3
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        fields = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    # a job whose traffic says that the program runs in child processes
    # (an agent and its workers) keeps this process off the chip: a
    # process that has touched it keeps it from every child. Platform,
    # device, trace, spans and registry are then the worker's own, handed
    # back in the job's result
    in_children = traffic.get("program_runs_in") == "child processes"

    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell['chips']}")
    sys.path.insert(0, ROOT)
    family = device = None
    if not in_children:
        try:
            family = load_family(fields["family"])
        except FamilyContractError as e:
            print(e, file=sys.stderr)
            return 2
        import jax

        found = jax.devices()
        device = {"platform": found[0].platform,
                  "kind": found[0].device_kind, "found": len(found)}
        refused = no_chip(device, cell, args)
        if refused:
            return refused
        if args.rehearsal:
            fields = {**fields, **family.REHEARSAL_FIELDS}

    job = importlib.import_module("benchmarks.jobs." + traffic["job"])
    result = job.run({
        "args": args, "cell": cell, "fields": fields, "traffic": traffic,
        "family": family, "t_start": T_START, "note": note, "root": ROOT,
    })
    if in_children:
        device = result["device"]  # as the first worker's JAX reported it
        refused = no_chip(device, cell, args)
        if refused:
            return refused
        fields = result["fields"]  # with the rehearsal's widths laid over

    peaks = None
    from benchmarks.harness import peaks as peaks_table, trace_reduce

    if device["platform"] == "tpu":
        peaks = peaks_table.lookup(device["kind"])  # unknown: an error
    device = {"platform": device["platform"], "kind": device["kind"],
              "count": cell["chips"]}
    if in_children:
        device["memory_peak_bytes"] = result["memory_peak_bytes"]
    else:
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in found[:cell["chips"]])

    out = {"correct": result["correct"], "attempted": result["attempted"],
           "failed": result["failed"], "metrics": {}, "device": device}
    if not args.trace:
        for m in metrics_of(cell["name"], bench["end_to_end"]):
            out["metrics"][m["name"]] = {
                "value": result["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        raw, reduced = None, []
        if result.get("trace_dir"):
            path = trace_reduce.find_xplane(result["trace_dir"])
            if path:
                raw = trace_reduce.load(path)
                reduced = trace_reduce.reduce(
                    raw, step_module=result["step_module"])
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:  # to look at a trace by hand; the driver never sets it
                shutil.copytree(result["trace_dir"], keep,
                                dirs_exist_ok=True)
            shutil.rmtree(result["trace_dir"], ignore_errors=True)
        ctx = {**result, "trace_raw": raw, "trace": reduced, "peaks": peaks,
               "fields": fields, "traffic": traffic, "cell": cell,
               "device": device}
        for m in metrics_of(cell["name"], bench["per_layer"]):
            value = load_reader(m["name"]).read(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {
                    "value": value, "unit": m["unit"]}
        if reduced:
            n = len(reduced)
            device["busy_s"] = sum(r["busy_s"] for r in reduced) / n
            device["window_s"] = sum(r["window_s"] for r in reduced) / n
            out["breakdown"] = {
                # an op's name is its whole HLO line: the head says enough
                "device_ops": [[name[:160], seconds] for name, seconds
                               in reduced[0]["device_ops"][:10]],
                "idle_gaps": [list(t) for t in reduced[0]["idle_gaps"][:10]],
            }
        elif args.rehearsal:
            device["busy_s"], device["window_s"] = 0.0, 0.0
        else:
            print("the traced run holds no device operation",
                  file=sys.stderr)
            return 4
    # every number compared beside its limit: the last lines of standard
    # error, and the result's last key
    out["compared"] = result.get("compared", {})
    for name, (value, limit) in out["compared"].items():
        print(f"compared {name}: {value!r} limit {limit!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
