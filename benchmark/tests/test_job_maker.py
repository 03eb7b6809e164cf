"""What a job carries: `JobMaker` keeps the id, the two `job` blocks, the
seed and the one generator; the family's `job_fields` says the rest. For
the families of the benchmark the jobs of a seed are the parent commit's,
byte for byte (`parent_jobs.json`, recorded at 0c66743 before `job_fields`
existed: per traffic file the probe, twelve jobs, the probe again), so no
cell's traffic moved."""

import importlib.util
import json
import random
from pathlib import Path

import pytest

from benchmark import harness

HERE = Path(__file__).resolve().parent
PARENT = json.loads((HERE / "parent_jobs.json").read_text())


def made(cell: str, seed: int, jobs: int) -> list[dict]:
    spec = harness.load_cell(cell)
    maker = harness.JobMaker(spec, seed, harness.load_family(spec["config"]))
    return ([maker.probe()] + [maker.next() for _ in range(jobs)]
            + [maker.probe()])


@pytest.mark.parametrize("traffic", sorted(PARENT))
def test_the_jobs_of_a_seed_are_the_parents_byte_for_byte(traffic):
    recorded = PARENT[traffic]
    (seed, want), = recorded["seeds"].items()
    got = made(recorded["cell"], int(seed), len(want) - 2)
    # as they are posted: the same keys in the same order, the same values
    assert [json.dumps(job) for job in got] == [json.dumps(j) for j in want]


def test_every_traffic_file_of_the_benchmark_is_recorded():
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    assert set(PARENT) == {cell["traffic"] for cell in bench["workloads"]}


def test_the_family_draws_first_and_the_probe_draws_nothing():
    """One generator: the family's draws, then the job's seed; the probe
    takes its seed from the traffic file and leaves the generator alone."""
    module_spec = importlib.util.spec_from_file_location(
        "stub_family", HERE / "stub_family.py")
    stub = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(stub)
    traffic = {"job": {"parameters": {"b": 2}}, "words": 2,
               "vocabulary": ["who", "what", "where"],
               "probe": {"prompt": "the probe", "seed": 77}}
    spec = {"cell": {"name": "cell"}, "traffic": traffic,
            "config": {"job": {"workflow": "w", "parameters": {"a": 1}}}}
    maker = harness.JobMaker(spec, 9, stub)
    probe, first, again = maker.probe(), maker.next(), maker.probe()
    rng = random.Random(9)
    words = [rng.choice(traffic["vocabulary"]) for _ in range(2)]
    assert first == {"workflow": "w", "parameters": {"a": 1, "b": 2},
                     "id": "cell-9-00002", "prompt": f"{' '.join(words)} 1?",
                     "seed": rng.getrandbits(31)}
    assert probe["prompt"] == again["prompt"] == "the probe"
    assert probe["seed"] == again["seed"] == 77
    assert (probe["id"], again["id"]) == ("cell-9-00001", "cell-9-00003")
