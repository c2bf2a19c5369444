"""Load a per-layer metric's reader file (``perf/layer_metrics/<name>.py``:
NAME, UNIT, LAYER, MOVES, SOURCE and ``read(snapshot)``) by its path; metric
names have dots, so the files are not importable by name.  A fault verb's file
(``perf/faults/<verb>.py``) is loaded the same way, under its own prefix."""

import importlib.util
import os


def load(path: str, prefix: str = "layer_metric_"):
    name = prefix + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
