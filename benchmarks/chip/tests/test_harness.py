"""CPU rehearsal of a whole run: the harness's driver end to end at fixture
cells found only by their files (one of them of an architecture that exists
only as fixture files), through the function entry; and the command line's
refusals."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import harness
from conftest import BENCH, HERE, ROOT

FIXTURES = HERE / "fixtures"
CELL = "tiny.tiny-mix"
END_TO_END = ("ttft_p90_s", "tpot_p90_ms", "itl_p90_ms", "slo_attainment", "decode_tok_s", "setup_s")
PER_LAYER = [("queue_wait_p90_ms", "ms"), ("select_ms_per_round", "ms"), ("token_gap_p90_ms", "ms"),
             ("device_idle_share", "%"), ("decode_step_roofline", "%"), ("fixture_rounds", "1")]


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


def run(seed, trace=False, seconds=2.0, cell=CELL):
    lines = []
    res = harness.run_cell(FIXTURES, cell, seed, seconds, trace, jax.devices()[0],
                           PER_LAYER if trace else (), log=lines.append)
    return res, lines


@pytest.mark.parametrize("cell", [CELL, "moe-tiny.tiny-mix"])
def test_run_is_correct_and_reports_every_end_to_end_metric(cell):
    res, lines = run(2**31 + 3, cell=cell)
    assert res["correct"] is True
    assert res["attempted"] == round(8.0 * 2.0) and res["failed"] == 0
    assert set(res["metrics"]) == set(END_TO_END)
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert lines[-2].startswith("check served_token_gap")
    assert lines[-1].startswith("check short_requests")
    json.loads(harness.json_line(res))


def test_end_to_end_numbers_do_not_read_the_programs_stamps(monkeypatch):
    from repro.core.request import Request

    def stamp(*_a, **_k):
        raise AssertionError("an end-to-end number read the program's own timing")

    for name in ("ttft", "mean_tpot", "meets_e2e", "meets_ttft", "meets_tpot"):
        monkeypatch.setattr(Request, name, stamp)
    res, _ = run(2**31 + 5)
    assert res["correct"] is True
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_same_seed_same_work_other_seed_same_sizes():
    a = harness.traffic.generate(harness.load_cell(FIXTURES, CELL).mix, 8.0, 2.0, 1.0, 5, 256)
    b = harness.traffic.generate(harness.load_cell(FIXTURES, CELL).mix, 8.0, 2.0, 1.0, 5, 256)
    c = harness.traffic.generate(harness.load_cell(FIXTURES, CELL).mix, 8.0, 2.0, 1.0, 6, 256)
    assert [(x.due, x.prompt, x.n_out) for x in a] == [(x.due, x.prompt, x.n_out) for x in b]
    win = [sorted((len(x.prompt), x.n_out) for x in y if x.counted) for y in (a, c)]
    assert sorted(x[0] for x in win[0]) == sorted(x[0] for x in win[1])
    assert [x.due for x in a] != [x.due for x in c]


def test_traced_run_reads_the_per_layer_metrics_it_can():
    res, _ = run(11, trace=True)
    assert res["correct"] is True
    # the CPU has no device plane and no peaks: device metrics stay silent,
    # host ones read
    assert {"queue_wait_p90_ms", "select_ms_per_round", "token_gap_p90_ms",
            "fixture_rounds"} <= set(res["metrics"])
    assert not {"device_idle_share", "decode_step_roofline"} & set(res["metrics"])
    assert res["metrics"]["fixture_rounds"]["value"] > 0


def _cli(args, cwd=ROOT, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _cli(["benchmarks/chip/run.py", "--workload", "minicpm-2b.decode-longtail",
              "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    p = _cli(["benchmarks/chip/run.py", "--workload", "minicpm-2b.decode-longtail",
              "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
