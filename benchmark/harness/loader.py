"""Finds everything a cell is made of by the names in BENCHMARK.json.

Nothing about a cell lives in Python. A cell (`workloads` entry) names a
configuration and a traffic mix; the mix names its job; every metric of
BENCHMARK.json has a file of its own naming its reader. A later PR adds
files and entries, and no file that is here needs an edit:

  benchmark/configs/<config>.json        sizes, source, sizing, reference
  benchmark/traffic/<mix>.json           "job" and that job's parameters
  benchmark/jobs/<job>.py                run(ctx) -> facts
  benchmark/end_to_end/<metric>.json     {"reader": "<file>:<function>", "args"}
  benchmark/layer_metrics/<metric>.json  the same, for a per-layer metric;
                                         `<metric>.<variant>` (one entry per
                                         end-to-end metric it moves) reads
                                         <metric>.json unless it has its own
  benchmark/readers/<file>.py            <function>(run, **args) -> number | None
  benchmark/references/<file>.py         the configuration's plain reference
  benchmark/kernel_costs/<kernel>.py     operations and bytes a kernel needs
  benchmark/opclasses/<job>.json         patterns sorting device ops into classes

A name that cannot be found is an error that lists the names that can.
"""
import dataclasses
import functools
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class UnknownName(LookupError):
    """A name in BENCHMARK.json or on the command line matches no file or
    entry."""


def _bench_dir(root):
    return os.path.join(root, "benchmark")


def benchmark_json(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _known(directory, suffix):
    try:
        return sorted(n[: -len(suffix)] for n in os.listdir(directory)
                      if n.endswith(suffix))
    except FileNotFoundError:
        return []


def load_json(kind, name, root=ROOT):
    """benchmark/<kind>/<name>.json as a dict."""
    directory = os.path.join(_bench_dir(root), kind)
    path = os.path.join(directory, name + ".json")
    if not os.path.isfile(path):
        raise UnknownName(f"no benchmark/{kind}/{name}.json; known {kind}: "
                          f"{_known(directory, '.json')}")
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module(path):
    """The Python file at `path`, executed once per process."""
    name = "benchmark_file_" + "".join(c if c.isalnum() else "_"
                                       for c in path)
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load_callable(kind, spec, root=ROOT):
    """The function named "<file>.py:<function>" under benchmark/<kind>/."""
    filename, _, func = spec.partition(":")
    directory = os.path.join(_bench_dir(root), kind)
    path = os.path.join(directory, filename)
    if not func or not os.path.isfile(path):
        raise UnknownName(f"no benchmark/{kind}/{spec}; known files: "
                          f"{_known(directory, '.py')}")
    try:
        return getattr(_module(path), func)
    except AttributeError:
        raise UnknownName(f"benchmark/{kind}/{filename} has no {func!r}") \
            from None


@dataclasses.dataclass
class Metric:
    """One entry of `end_to_end` or `per_layer` with its reader."""
    name: str
    unit: str
    entry: dict       # the BENCHMARK.json entry
    reader: object    # callable(run, **args) -> number | None
    args: dict


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list  # [Metric] this cell reports with --trace 0
    per_layer: list   # [Metric] this cell reports with --trace 1


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"no {what} named {name!r}; known: "
                      f"{[e['name'] for e in entries]}")


def _metric_spec(kind, name, root):
    """The file of metric `name`. BENCHMARK.json gives a metric one `moves`,
    so a per-layer metric that moves one end-to-end metric in one cell and
    another in the next has an entry per variant, `<metric>.<variant>`, and
    one file, <metric>.json."""
    stem = name.rpartition(".")[0]
    own = os.path.join(_bench_dir(root), kind, name + ".json")
    return load_json(kind, stem if stem and not os.path.isfile(own) else name,
                     root)


def _metrics(entries, kind, cell_name, root):
    out = []
    for e in entries:
        if "workloads" in e and cell_name not in e["workloads"]:
            continue
        spec = _metric_spec(kind, e["name"], root)
        out.append(Metric(e["name"], e["unit"], e,
                          load_callable("readers", spec["reader"], root),
                          spec.get("args", {})))
    return out


def _rehearsal(d):
    """`d` with its "rehearse" overrides applied (one level deep)."""
    out = {k: v for k, v in d.items() if k != "rehearse"}
    out.update(d.get("rehearse", {}))
    return out


def load_cell(name, root=ROOT, rehearse=False):
    """The cell `name` with its configuration, mix and metrics resolved.
    With `rehearse`, the tiny sizes under each file's "rehearse" key take
    the place of the real ones (CPU walk-through, never a measurement)."""
    bj = benchmark_json(root)
    w = _by_name(bj["workloads"], name, "workload")
    c = _by_name(bj["configs"], w["config"], "config")
    prefix = "benchmark/configs/"
    if not (c["file"].startswith(prefix) and c["file"].endswith(".json")):
        raise UnknownName(f"config file {c['file']!r} is not {prefix}*.json")
    config = load_json("configs", c["file"][len(prefix):-len(".json")], root)
    traffic = load_json("traffic", w["traffic"], root)
    if rehearse:
        config, traffic = _rehearsal(config), _rehearsal(traffic)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=_metrics(bj["end_to_end"], "end_to_end", name,
                                    root),
                per_layer=_metrics(bj["per_layer"], "layer_metrics", name,
                                   root))


def load_job(traffic, root=ROOT):
    return load_callable("jobs", f"{traffic['job']}.py:run", root)


def load_reference(config, root=ROOT):
    """The configuration's plain reference, "<file>.py:<function>"."""
    return load_callable("references", config["reference"], root)


def load_opclasses(job, root=ROOT):
    return load_json("opclasses", job, root)


def peaks(device_kind, root=ROOT):
    """Published per-chip peaks for `device_kind`; unknown is an error,
    never a default."""
    with open(os.path.join(_bench_dir(root), "harness", "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise UnknownName(f"no published peaks for device_kind "
                          f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
