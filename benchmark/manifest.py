"""``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the NAME in
``BENCHMARK.json``:

  configs[].file                      the configuration as it is run
  benchmark/traffic/<traffic>.json    parameters for the one generator
  benchmark/metrics/<metric>.json     a per-layer metric: which reader
  benchmark/readers/<reader>.py       reads it from counters / spans / trace

A later PR adds files and entries and edits nothing that is there.
"""

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def _need(cond, msg):
    if not cond:
        raise ManifestError(msg)


def check_name(name, what):
    _need(isinstance(name, str) and NAME_RE.match(name),
          "{} {!r}: letters, digits, '_', '.', '-' only, at most 64".format(
              what, name))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(path=None):
    """Parse and validate ``BENCHMARK.json``; returns the dict."""
    m = load_json(path or os.path.join(ROOT, "BENCHMARK.json"))
    validate(m)
    return m


def validate(m):
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    _need(set(m) == keys, "BENCHMARK.json keys {} != {}".format(
        sorted(m), sorted(keys)))
    _need(isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51,
          "run_seconds must be a whole number from 1 to 51")
    configs = {}
    for c in m["configs"]:
        check_name(c["name"], "config")
        _need(c["name"] not in configs, "config {} twice".format(c["name"]))
        _need(any(c["file"].startswith(p + "/") for p in m["paths"]),
              "config file {} lies outside paths".format(c["file"]))
        for k in c["reduced"]:
            check_name(k, "reduced key")
        configs[c["name"]] = c
    cells, pairs = {}, set()
    for w in m["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["traffic"], "traffic")
        _need(w["name"] not in cells, "workload {} twice".format(w["name"]))
        _need(w["config"] in configs, "workload {} names no config".format(
            w["name"]))
        _need((w["config"], w["traffic"]) not in pairs,
              "config/traffic pair of {} appears twice".format(w["name"]))
        _need(w["chips"] in (1, 4), "chips is 1 or 4")
        _need(0 < len(w["why"]) <= 200 and "\n" not in w["why"],
              "why of {}: one line, at most 200 characters".format(w["name"]))
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    _need({w["config"] for w in m["workloads"]} == set(configs),
          "every config is used by some cell")
    e2e = {}
    for x in m["end_to_end"]:
        check_name(x["name"], "metric")
        _need(x["name"] not in e2e, "metric {} twice".format(x["name"]))
        _need(UNIT_RE.match(x["unit"]), "unit {!r}".format(x["unit"]))
        _need(x["better"] in ("lower", "higher"), "better")
        _need(x["source"] in ("host_clock", "device_trace"),
              "an end-to-end metric is taken by the benchmark itself")
        _need(0 < x["bound"] <= 0.1, "bound of {} in (0, 0.1]".format(x["name"]))
        for c in x.get("workloads", ()):
            _need(c in cells, "{} lists unknown cell {}".format(x["name"], c))
        e2e[x["name"]] = x
    _need("setup_s" in e2e, "setup_s is an end-to-end metric of every cell")
    names = set(e2e)
    for x in m["per_layer"]:
        check_name(x["name"], "metric")
        _need(x["name"] not in names, "metric {} twice".format(x["name"]))
        names.add(x["name"])
        _need(UNIT_RE.match(x["unit"]), "unit {!r}".format(x["unit"]))
        _need(x["better"] in ("lower", "higher"), "better")
        _need(x["source"] in SOURCES, "source of {}".format(x["name"]))
        _need(x["moves"] in e2e, "{} moves no end-to-end metric".format(
            x["name"]))
        for c in x.get("workloads", cells):
            _need(c in cells, "{} lists unknown cell {}".format(x["name"], c))
            _need(c in metric_cells(m, e2e[x["moves"]]),
                  "{}: cell {} does not report {}".format(
                      x["name"], c, x["moves"]))
    for name in cells:
        mine = [x for x in m["end_to_end"] if name in metric_cells(m, x)]
        _need(len(mine) >= 2, "cell {} reports setup_s and one more".format(
            name))
        _need(any(name in metric_cells(m, x) for x in m["per_layer"]),
              "cell {} reports no per-layer metric".format(name))


def metric_cells(m, metric):
    """The cells that report ``metric``."""
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


def cell(m, name):
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError("no workload {!r} in BENCHMARK.json (known: {})".format(
        name, ", ".join(w["name"] for w in m["workloads"])))


def config_of(m, w):
    entry = next(c for c in m["configs"] if c["name"] == w["config"])
    return entry, load_json(os.path.join(ROOT, entry["file"]))


def traffic_of(w):
    return load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))


def metrics_of(m, cell_name, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    return [x for x in m[group] if cell_name in metric_cells(m, x)]


def reader_of(metric_name):
    """``(spec, read)`` of a per-layer metric: its data file and the
    ``read(ctx)`` of the reader module the file names."""
    spec = load_json(os.path.join(HERE, "metrics", metric_name + ".json"))
    check_name(spec["reader"], "reader")
    module = importlib.import_module("readers." + spec["reader"])
    return spec, module.read
