"""Repo-native lint configuration: the invariants, spelled as data.

swarmlint is not a general-purpose linter — every constant here names a
specific contract this repository's architecture depends on. Changing a
value below is changing an invariant; do it in the PR that changes the
architecture, with the reasoning in the commit.
"""

from __future__ import annotations

# directories (relative to the repo root) whose *.py files are linted;
# tests/ is deliberately excluded — fixtures embed rule-positive snippets
SCAN_PATHS = ("chiaswarm_tpu", "tools")

# directory names never descended into
EXCLUDE_DIRS = ("__pycache__",)

# --- SW001: jax purity ------------------------------------------------------

# top-level package names that must never be imported (at module level,
# transitively) from the jax-free roots: the hive coordinates from
# chip-less hosts, so its import closure must not pull an accelerator
# runtime. Function-local (lazy) imports are the sanctioned escape hatch
# and are NOT counted — they only execute on worker-side call paths.
ACCELERATOR_PACKAGES = ("jax", "jaxlib", "flax", "torch", "transformers",
                        "diffusers")

# modules / packages (repo-relative paths) declared jax-free. A path
# naming a directory covers every module under it.
JAXFREE_ROOTS = (
    "chiaswarm_tpu/hive_server",
    "chiaswarm_tpu/coalesce.py",
    "chiaswarm_tpu/text_families.py",
    "chiaswarm_tpu/telemetry.py",
    "chiaswarm_tpu/outbox.py",
    "chiaswarm_tpu/settings.py",
    "chiaswarm_tpu/faults.py",
    "chiaswarm_tpu/log_setup.py",
    "tools/swarm_top.py",
    "tools/hive_serve.py",
)

# --- SW002: event-loop blocking calls ---------------------------------------

# (module, attr) calls that block the calling thread; inside an
# ``async def`` body they stall every coroutine on the loop (heartbeats,
# cancel piggybacks, /metrics scrapes). Route them through
# run_in_executor / asyncio.to_thread instead.
BLOCKING_MODULE_CALLS = frozenset({
    ("time", "sleep"),
    ("subprocess", "run"),
    ("subprocess", "call"),
    ("subprocess", "check_call"),
    ("subprocess", "check_output"),
    ("subprocess", "Popen"),
    ("os", "fsync"),
    ("os", "system"),
    ("os", "popen"),
    # file-handle json codec: parsing a multi-MB result envelope on the
    # loop was the recurring bug SW002 exists for. The string variants
    # (loads/dumps) are left to review — small control payloads are fine
    # and the hive already routes big bodies through asyncio.to_thread.
    ("json", "load"),
    ("json", "dump"),
    ("socket", "create_connection"),
    ("urllib", "urlopen"),
    ("requests", "get"),
    ("requests", "post"),
})

# method names that are sync file I/O whatever the receiver (the pathlib
# idiom this repo uses everywhere)
BLOCKING_METHOD_NAMES = frozenset({
    "read_text", "read_bytes", "write_text", "write_bytes",
})

# bare-name calls that block (the builtin)
BLOCKING_NAME_CALLS = frozenset({"open"})

# --- SW003: clock discipline ------------------------------------------------

HIVE_SERVER_DIR = "chiaswarm_tpu/hive_server"
CLOCK_MODULE = "chiaswarm_tpu/hive_server/clock.py"
# the two faces HiveClock wraps; time.perf_counter for pure local
# durations is allowed (it never crosses a persistence or API boundary)
CLOCK_CALLS = frozenset({("time", "time"), ("time", "monotonic")})

# --- SW004 / SW005 / SW006: drift rules -------------------------------------

SETTINGS_FILE = "chiaswarm_tpu/settings.py"
README_FILE = "README.md"
SETTINGS_TEST_FILE = "tests/test_settings.py"
JOURNAL_FILE = "chiaswarm_tpu/hive_server/journal.py"
REPLICATION_FILE = "chiaswarm_tpu/hive_server/replication.py"

# metric registrations are collected from the package only — tools/ and
# tests/ READ exposition text and would contribute false names
METRICS_SCAN_PREFIX = "chiaswarm_tpu"
METRIC_PREFIX = "swarm_"

# --- SW007: unbounded caches ------------------------------------------------

# a dict/OrderedDict/defaultdict assigned to a target whose name matches
# this substring (case-insensitive) is presumed a cache and must show
# eviction (.popitem) somewhere in the same file
CACHE_NAME_SUBSTRING = "cache"
# cache dicts whose names don't say so (the PR 13 compiled-program
# variants that motivated this rule)
CACHE_EXTRA_NAMES = frozenset({"_programs"})
