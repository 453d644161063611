import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def load(relpath: str):
    path = os.path.join(BENCH, relpath)
    name = "bench_" + os.path.splitext(relpath)[0].replace("/", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def btc():
    return load("configs/btc-sha256d.py")


@pytest.fixture(scope="session")
def ltc():
    return load("configs/ltc-scrypt.py")
