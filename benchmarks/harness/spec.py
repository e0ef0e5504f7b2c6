"""Finding things by name. ``BENCHMARK.json`` names a cell's
configuration and traffic mix and each metric; every one of them is a
file of its own under ``benchmarks/``, so adding one is adding a file
and one entry — never an edit here."""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module. Metric names carry
    dots, so the import goes by path and not by dotted name."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic
    and the metrics it reports, as ``BENCHMARK.json`` states them."""

    def __init__(self, workload):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; BENCHMARK.json has "
                f"{sorted(cells)}"
            )
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        with open(os.path.join(ROOT, configs[self.entry["config"]]["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.end_to_end = [
            m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])
        ]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)
        ]
