"""BENCHMARK.json and the data files it names, found by name.

A cell is one entry of ``workloads``. Its configuration is
``configs/<config>.json`` (+ ``.siddhi`` app text, + ``.py`` plain reference),
its traffic mix is ``traffic/<traffic>.json`` and what is particular to the
cell (a paced cell's fixed rate) is ``cells/<cell>.json``, merged over the
mix. A per-layer metric is read by ``metrics/<name>.py``. Adding a cell, a
mix, a configuration or a metric adds files and one entry and edits nothing.

A configuration whose deployment is too large for a CPU test carries a
``small`` block: the sizes the yardstick's own tests and ``--rehearsal``
run it at, and nothing a timed run ever sees (``Cell(..., small=True)``)::

    "small": {"config": {<keys of the configuration, replaced>},
              "app": "<name>.small.siddhi",   (optional: the same query at
                                               smaller engine sizes)
              "interpreter_events": 9000, "interpreter_rows_min": 50,
              "control_events": 60000}        (each optional)
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(path: str | None = None) -> dict:
    return _json(path or os.path.join(ROOT, "BENCHMARK.json"))


def load_module(path: str, name: str):
    """Import a file whose name need not be an identifier
    (``configs/pattern-chain8.py``, ``metrics/step.host_ms_per_batch.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    # what the yardstick's tests draw where the configuration says nothing
    TEST_SIZES = {"interpreter_events": 9000, "interpreter_rows_min": 50,
                  "control_events": 60_000}

    def __init__(self, manifest: dict, name: str, small: bool = False):
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            known = [w["name"] for w in manifest["workloads"]]
            raise KeyError(f"no workload '{name}' in BENCHMARK.json "
                           f"(it has {known})")
        self.manifest = manifest
        self.name = name
        self.small = small
        self.chips = int(entry["chips"])
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == entry["config"])
        self.config_name = cfg_entry["name"]
        cfg_path = os.path.join(ROOT, cfg_entry["file"])
        self.config = _json(cfg_path)
        stem = cfg_path[:-len(".json")]
        app_path = stem + ".siddhi"
        # a timed run drops the block unread; only `small` sizes come from it
        block = self.config.pop("small", None) or {}
        self.test_sizes = dict(self.TEST_SIZES)
        if small:
            self.config.update(block.get("config", {}))
            self.test_sizes.update({k: int(block[k]) for k in self.TEST_SIZES
                                    if k in block})
            if "app" in block:
                app_path = os.path.join(os.path.dirname(cfg_path),
                                        block["app"])
        with open(app_path, encoding="utf-8") as f:
            self.app_text = f.read()
        self.reference = load_module(stem + ".py",
                                     "bench_reference_" + re.sub(
                                         r"\W", "_", self.config_name))
        self.traffic_name = entry["traffic"]
        self.traffic = _json(os.path.join(BENCH_DIR, "traffic",
                                          entry["traffic"] + ".json"))
        cell_file = os.path.join(BENCH_DIR, "cells", name + ".json")
        if os.path.exists(cell_file):
            self.traffic.update(_json(cell_file))
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if "workloads" not in m or name in m["workloads"]]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in manifest["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e_names)]

    def shrunk(self) -> "Cell":
        """This cell at its configuration's ``small`` sizes."""
        return self if self.small else Cell(self.manifest, self.name,
                                            small=True)


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``: the metric's value, or None
    where it found nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    return load_module(path, "bench_metric_" + re.sub(r"\W", "_", name)).read
