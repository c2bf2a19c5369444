"""Load a per-layer metric's reader file (``perf/layer_metrics/<name>.py``:
NAME, UNIT, LAYER, MOVES, SOURCE and ``read(snapshot)``) by its path; metric
names have dots, so the files are not importable by name."""

import importlib.util
import os


def load(path: str):
    name = "layer_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
