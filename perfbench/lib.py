"""What every part of the benchmark shares: where its files are, how
a cell is read from BENCHMARK.json and the data files it names, the
event lines a process that holds the chip prints for its parent, and
the rule that anything but the asked-for TPU is a failure.

Nothing here imports jax at module level: run.py and the load
generator import this file and must never touch the chip.
"""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
EVENT = "PERFBENCH "  # prefix of the machine-read lines a child prints


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """One entry of `workloads` with its configuration and traffic
    files read in — found by the names BENCHMARK.json gives, so a new
    cell is a new entry and new files, never an edit here."""
    manifest = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    return fill_cell(manifest, dict(cells[workload]))


def fill_cell(manifest: dict, cell: dict) -> dict:
    """A `workloads` entry with its files read in and the metrics it
    reports picked out of the manifest."""
    workload = cell["name"]
    configs = {c["name"]: c for c in manifest["configs"]}
    cell["config_entry"] = configs[cell["config"]]
    cell["model"] = read_json(os.path.join(ROOT, cell["config_entry"]["file"]))
    cell["mix"] = read_json(
        os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
    )

    def reported(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if reported(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if reported(m)]
    return cell


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str):
    return load_module(
        os.path.join(BENCH, "drivers", name + ".py"), "perfbench_driver_" + name
    )


def read_layer_metric(name: str, run: dict):
    """The value of one per-layer metric from a run's events and
    reduced trace, or None where its reader finds nothing to read."""
    module = load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"),
    )
    return module.read(run)


def emit(**event) -> None:
    print(EVENT + json.dumps(event), flush=True)


def parse_event(line: str):
    if line.startswith(EVENT):
        return json.loads(line[len(EVENT):])
    return None


def log(msg: str) -> None:
    print(msg, flush=True)


def require_device(rehearsal: bool, chips: int) -> dict:
    """The device as jax reports it. Anything but the asked-for TPU is
    a failure, not a smaller run (the rehearsal: anything but cpu)."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want:
        raise RuntimeError(
            f"needs platform {want!r}, jax found {device['platform']!r} "
            f"({device['kind']})"
        )
    if not rehearsal and device["count"] != chips:
        raise RuntimeError(
            f"needs {chips} chip(s), jax found {device['count']}"
        )
    return device


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend
    keeps no such count, as the CPU's does not)."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks))


def peaks_for(device_kind: str) -> dict:
    table = read_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise RuntimeError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json"
        )
    return table["devices"][device_kind]


def llama_config(model: dict, rehearsal: bool):
    """The program's config object for one configuration file. The
    rehearsal keeps the control flow and swaps in the repo's tiny
    sizes; it proves nothing about the chip."""
    import dataclasses

    import jax.numpy as jnp

    from dlrover_tpu.models import llama

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    run = model["run"]
    if rehearsal:
        return dataclasses.replace(
            llama.LlamaConfig.tiny(),
            n_layers=2, attn_impl="auto",
            param_dtype=dtypes[run["param_dtype"]],
            dtype=jnp.float32, max_seq_len=256,
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]),
        )
    return llama.LlamaConfig(
        vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        mlp_dim=model["intermediate_size"],
        max_seq_len=run["max_seq_len"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["param_dtype"]],
        tie_embeddings=bool(model["tie_word_embeddings"]),
        attn_impl="auto",
    )


def kernel_names(compiled_text: str) -> list:
    """Names of the Pallas kernels inside a compiled program (the
    instructions whose target is `tpu_custom_call`)."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = line.strip().split(" = ", 1)[0]
        head = head.replace("ROOT ", "").lstrip("%")
        names.add(head.rsplit(".", 1)[0] if "." in head else head)
    return sorted(names)


class CompileCounter:
    """Counts the backend compilations jax reports while `counting`
    is on: a steady window must count none."""

    EVENT_NAME = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_args, **_kw):
        if self.counting and name == self.EVENT_NAME:
            self.count += 1


def end_to_end_metrics(cell: dict, values: dict) -> dict:
    """The cell's end-to-end metrics in the result line's form; a
    metric the manifest lists for this cell and the run did not
    measure is an error, not a gap."""
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in cell["end_to_end"]
    }


def layer_metrics(cell: dict, run: dict) -> dict:
    """Each per-layer metric the manifest lists for this cell, read by
    its own file; one whose reader finds nothing is left out."""
    out = {}
    for m in cell["per_layer"]:
        value = read_layer_metric(m["name"], run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Checks:
    """Every number compared, printed beside its limit, and kept
    (`compared`) for the result line's last key and the run's last
    lines on standard error."""

    def __init__(self):
        self.ok = True
        self.compared = {}

    def at_most(self, name, value, limit):
        good = value == value and value <= limit
        self.ok &= good
        self.compared[name] = {
            "value": value if value == value else None, "limit": limit}
        log(f"CHECK {name} value={value!r} limit={limit!r} "
                f"{'ok' if good else 'FAILED'}")

    def require(self, name, good, detail=""):
        self.ok &= bool(good)
        # a condition counts as the number of times it was broken
        self.compared[name] = {"value": 0 if good else 1, "limit": 0}
        log(f"CHECK {name} {'ok' if good else 'FAILED'} {detail}")
