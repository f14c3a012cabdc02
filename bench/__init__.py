"""The chip benchmark: harness, yardstick and data (see PERF.md)."""
import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """The module in file `path` under `bench/`, found by its name."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
