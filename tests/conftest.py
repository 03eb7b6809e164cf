"""Test configuration: hermetic CPU backend with 8 virtual devices.

Mesh/sharding paths are exercised without TPU hardware by forcing the JAX CPU
platform and splitting the host into 8 virtual devices (SURVEY §4 test
strategy). Must run before the first `import jax` anywhere in the test
process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Load torch's native runtime BEFORE jax's, and pin it to one thread.
# The torch-parity modules import torch lazily mid-suite; on this
# jax/torch build the first parity test — landing after 20+ jax tests
# have warmed XLA's thread pools — segfaults the whole process in native
# code (reproduced on the pristine seed tree, so it predates any repo
# code; the classic OpenMP/oneDNN runtime clash). The parity models are
# tiny, so a single-threaded torch costs nothing.
os.environ.setdefault("MKL_THREADING_LAYER", "GNU")
try:
    import torch

    torch.set_num_threads(1)
    torch.set_num_interop_threads(1)
except ImportError:
    pass

# The env vars above act only if they are set before jax is first imported;
# say it through jax.config too, which also holds when something imported
# jax earlier.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

# --- quick tier (VERDICT r04 weak #6: the full hermetic suite is an
# hour-plus single-process, which discourages running anything before a
# TPU bench window). `pytest -m quick` selects the fast hermetic modules
# below (unit/contract tests with no full-model builds); everything else
# is marked `heavy`. CI runs the whole suite either way.
_QUICK_MODULES = {
    "test_allocator",
    "test_batching",
    "test_external_resources",
    "test_faults",
    "test_flash_attention",
    "test_geglu_numerics",
    "test_hive_protocol",
    "test_hive_replication",
    "test_job_arguments",
    "test_loras",
    "test_mpeg_audio",
    "test_outbox",
    "test_outbox_inspect",
    "test_output_processor",
    "test_placement_stats",
    "test_registry_exhaustive",
    "test_requirements",
    "test_schedulers",
    "test_settings",
    "test_telemetry",
    "test_tokenizer",
    "test_weights_path",
    "test_worker_failover",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast hermetic tier (pytest -m quick, <10 min)")
    config.addinivalue_line(
        "markers", "heavy: full-model / e2e tests excluded from -m quick")


import functools  # noqa: E402
import re  # noqa: E402


_TORCH_USE = re.compile(
    r"^\s*(import torch|from torch)|importorskip\([\"']torch"
    r"|torch_unet_ref|torch_svd_ref|torch_cascade_ref",
    re.M,
)


@functools.lru_cache(maxsize=None)
def _module_uses_torch(path: str) -> bool:
    try:
        with open(path, encoding="utf-8") as f:
            return _TORCH_USE.search(f.read()) is not None
    except OSError:
        return False


# Modules whose tests spawn whole child processes (chaos scenarios,
# restarts: each a fresh interpreter + jax compile set) or compile a
# whole pipeline family in-process from scratch (the IF cascade pair
# and the svd golden-workflow module each jit multi-minute program
# sets on a 1-core box; ~50 s per test, versus ~2 s for the median
# unit test). On a small CI box these dominate the suite's wall
# clock; they sort AFTER the in-process tests (same rationale as the
# torch ordering below: bank the hundreds of cheap results first, so
# an external timeout chops the expensive integration tail rather
# than the unit tests that happen to sort after them alphabetically).
# They still run exactly once, and still before the torch group — a
# torch segfault must not eat them.
_HEAVY_TAIL_MODULES = {"test_chaos_smoke", "test_chip_smoke",
                       "test_compile_cache", "test_dag_svd", "test_cascade",
                       "test_deepfloyd", "test_depth"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        name = item.module.__name__.rsplit(".", 1)[-1]
        item.add_marker(
            pytest.mark.quick if name in _QUICK_MODULES
            else pytest.mark.heavy
        )
    # Run every torch-executing module LAST. On this jax/torch build a
    # torch/transformers forward segfaults the whole process once enough
    # other native work has accumulated (reproduced on the pristine seed
    # tree: the suite died at test #22, the first CLAP parity forward;
    # neither import order, nor single-threaded torch, nor running the
    # torch modules first dodges it, and each crashing combination passes
    # in isolation). Sorting the torch-parity/conversion modules to the
    # end lets the ~430 jax-only tests bank their results before the
    # first at-risk forward; the torch modules themselves all pass when
    # run standalone. Stable sort: alphabetical order is preserved within
    # each group, and every test still runs exactly once.
    def _order(item):
        if _module_uses_torch(str(item.fspath)):
            return 2
        name = item.module.__name__.rsplit(".", 1)[-1]
        return 1 if name in _HEAVY_TAIL_MODULES else 0

    items.sort(key=_order)


@pytest.fixture()
def sdaas_root(tmp_path, monkeypatch):
    """Isolated settings/cache root so tests never touch ~/.sdaas."""
    monkeypatch.setenv("SDAAS_ROOT", str(tmp_path / "sdaas"))
    for var in ("SDAAS_TOKEN", "SDAAS_URI", "SDAAS_WORKERNAME"):
        monkeypatch.delenv(var, raising=False)
    return tmp_path / "sdaas"
