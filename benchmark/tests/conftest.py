"""Benchmark tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests

``toy_root`` is a checkout-like directory in which the test data's
throwaway cells (``data/``) are added to the repo's benchmark as new files
and new entries only."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session", autouse=True)
def _compile_cache(tmp_path_factory):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark")
    data = os.path.join(HERE, "data")
    added = []
    for sub in ("configs", "traffic", "metrics"):
        for name in sorted(os.listdir(os.path.join(data, sub))):
            dst = root / "benchmark" / sub / name
            assert not dst.exists(), f"test data would overwrite {dst}"
            shutil.copy(os.path.join(data, sub, name), dst)
            added.append(dst)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(data, "extra_cells.json")) as f:
        extra = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += extra[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.get("workloads", []).extend(extra["join"].get(m["name"], []))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return str(root)


@pytest.fixture(scope="module")
def sim_crypto():
    """The program's sim signature scheme, which the generator's
    signatures follow, so that the oracle accepts its events."""
    from tpu_swirld import crypto

    before = crypto.backend_name()
    crypto.set_backend("sim")
    yield
    crypto.set_backend(before)


@pytest.fixture
def cpu_devices():
    import jax

    return lambda chips: jax.devices()[:chips]
