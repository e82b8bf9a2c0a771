"""The cells the bench tests run over, found from the files under fixtures/:

* `<cell>.readings.json` -- readings bench/calibrate.py took on the chip at
  the cell's own size, judged against bench/limits/<cell>.json;
* `<cell>.cpu.json` -- the cell at a size a CPU test run can hold: keys of
  the configuration and of the traffic mix to override, and limits for that
  size with the readings they were set from.

A new cell brings its own files here; no test is edited."""
import glob
import json
import os

import run

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _cells(suffix: str) -> list:
    return sorted(os.path.basename(p)[: -len(suffix)]
                  for p in glob.glob(os.path.join(FIXTURES, "*" + suffix)))


def reading_cells() -> list:
    return _cells(".readings.json")


def cpu_cells(kind: str) -> list:
    return [c for c in _cells(".cpu.json") if run.load_spec(c).traffic["kind"] == kind]


def readings(cell: str) -> dict:
    with open(os.path.join(FIXTURES, cell + ".readings.json")) as fh:
        return json.load(fh)


def cpu_spec(cell: str) -> run.Spec:
    """The cell's spec with its CPU-size overrides applied."""
    with open(os.path.join(FIXTURES, cell + ".cpu.json")) as fh:
        small = json.load(fh)
    spec = run.load_spec(cell)
    spec.config = dict(spec.config, **small["config"])
    spec.traffic = dict(spec.traffic, **small["traffic"])
    spec.limits = small["limits"]
    return spec
