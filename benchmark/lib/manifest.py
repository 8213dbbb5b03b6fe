"""BENCHMARK.json and the files it names.  Everything that belongs to
one configuration, one traffic mix, one per-layer metric or one cell's
limits is a file of its own, found by the name in the manifest, so that a
later PR adds a cell or a metric by adding files and entries."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest(root=ROOT):
    return _load(root, "BENCHMARK.json")


def workload(man, name):
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit("no workload %r in BENCHMARK.json (has: %s)" % (
        name, ", ".join(w["name"] for w in man["workloads"])))


def config(man, name, root=ROOT, rehearse=False):
    for c in man["configs"]:
        if c["name"] == name:
            cfg = _load(root, c["file"])
            if rehearse:
                cfg.update(cfg.get("rehearsal", {}))
            return cfg
    raise SystemExit("no configuration %r in BENCHMARK.json" % name)


def traffic(name, root=ROOT, rehearse=False):
    t = _load(root, "benchmark", "traffic", name + ".json")
    if rehearse:
        t.update(t.get("rehearsal", {}))
    return t


def limits(workload_name, root=ROOT):
    return _load(root, "benchmark", "limits", workload_name + ".json")


def layer_metric(name, root=ROOT):
    return _load(root, "benchmark", "layer_metrics", name + ".json")


def metrics_of(man, kind, workload_name):
    """The manifest's metrics of one kind (``end_to_end`` or
    ``per_layer``) that this cell reports."""
    e2e = {m["name"]: m for m in man["end_to_end"]}

    def reports_e2e(m):
        return "workloads" not in m or workload_name in m["workloads"]

    if kind == "end_to_end":
        return [m for m in man["end_to_end"] if reports_e2e(m)]
    return [m for m in man["per_layer"]
            if (workload_name in m["workloads"] if "workloads" in m
                else reports_e2e(e2e[m["moves"]]))]


def check(man, root=ROOT):
    """Every name, unit and file the manifest gives resolves and keeps to
    the allowed characters; raises ValueError on the first that does not."""
    def name_ok(n):
        if not NAME.match(n):
            raise ValueError("bad name %r" % (n,))

    def line_ok(text):
        if not (1 <= len(text) <= 200) or "\n" in text or "\t" in text:
            raise ValueError("not one line of 1 to 200 characters: %r" % text)

    def keys_ok(entry, must, may=()):
        extra = set(entry) - set(must) - set(may)
        if extra or set(must) - set(entry):
            raise ValueError("%s: keys must be %s" % (entry.get("name"), must))

    if set(man) != {"command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"}:
        raise ValueError("BENCHMARK.json has other keys than the contract's")

    cfgs = {c["name"] for c in man["configs"]}
    cells = {w["name"] for w in man["workloads"]}
    for c in man["configs"]:
        keys_ok(c, ("name", "source", "file", "reduced", "why"))
        name_ok(c["name"]), line_ok(c["source"]), line_ok(c["why"])
        config(man, c["name"], root)
    for w in man["workloads"]:
        keys_ok(w, ("name", "config", "traffic", "chips", "why"))
        name_ok(w["name"]), name_ok(w["traffic"]), line_ok(w["why"])
        if w["config"] not in cfgs:
            raise ValueError("workload %s names no configuration" % w["name"])
        traffic(w["traffic"], root)
        limits(w["name"], root)
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["end_to_end"] + man["per_layer"]:
        name_ok(m["name"])
        if not UNIT.match(m["unit"]):
            raise ValueError("bad unit %r of %s" % (m["unit"], m["name"]))
        if m["better"] not in ("lower", "higher"):
            raise ValueError("bad 'better' of %s" % m["name"])
        for wn in m.get("workloads", []):
            if wn not in cells:
                raise ValueError("%s lists no cell %r" % (m["name"], wn))
    for m in man["end_to_end"]:
        keys_ok(m, ("name", "unit", "better", "bound", "source"),
                ("workloads",))
        if not 0 < m["bound"] <= 0.1:
            raise ValueError("bound of %s outside (0, 0.1]" % m["name"])
    for m in man["per_layer"]:
        keys_ok(m, ("name", "unit", "better", "source", "layer", "moves"),
                ("workloads",))
        line_ok(m["layer"])
        if m["moves"] not in e2e:
            raise ValueError("%s moves no end-to-end metric" % m["name"])
        spec = layer_metric(m["name"], root)
        path = os.path.join(root, "benchmark", "lib", "reducers",
                            spec["reducer"] + ".py")
        if not os.path.exists(path):
            raise ValueError("%s: no reducer %s" % (m["name"], spec["reducer"]))
    return True
