"""chip_smoke.py's phases on the CPU at minicpm-2b-smoke size (kernels
interpreted): the same functions the chip run calls, so a broken phase
shows here before it costs a chip run. Only `main` demands the TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke as cs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import sizing  # noqa: E402
from repro.launch.sizing import choose_size, describe  # noqa: E402
from repro.models import build_model  # noqa: E402

SMOKE = "minicpm-2b-smoke"
PROMPTS, OUTPUTS = (8, 40), (4, 8)


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config(SMOKE)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _trace(cfg, n=4):
    return cs.make_trace(cfg.vocab_size, n, seed=0, prompt_range=PROMPTS,
                         output_range=OUTPUTS, gap=0.01)


def test_make_trace_is_seeded_and_in_range(smoke):
    cfg, _, _ = smoke
    a, b = _trace(cfg), _trace(cfg)
    assert [p for _, p in a] == [p for _, p in b]
    for req, prompt in a:
        assert PROMPTS[0] <= req.input_len == len(prompt) <= PROMPTS[1]
        assert OUTPUTS[0] <= req.output_len <= OUTPUTS[1]


def _with_limit(monkeypatch, limit):
    """The CPU reports no memory limit: give it one, as a chip would."""
    monkeypatch.setattr(sizing, "device_limit", lambda device: limit)


def test_agreement_counts_first_tokens_shares_and_prefixes():
    a = {0: [5, 6, 7, 8], 1: [1, 2], 2: [9]}
    b = {0: [5, 6, 0, 8], 1: [3, 2], 2: [9]}
    got = cs.agreement(a, b)
    assert got == dict(first=2, share=5 / 7, tokens=7, prefix=[2, 0, 1])


def test_choose_size_takes_widest_that_fits(smoke, monkeypatch):
    _, model, _ = smoke
    dev = jax.devices()[0]
    sizes = ((8, 128), (4, 128))
    kw = dict(sizes=sizes, chunk=16)
    _with_limit(monkeypatch, 1 << 40)
    slots, max_len, rep = choose_size(model, 4, 48, dev, **kw)
    assert (slots, max_len) == (8, 128) and rep["need_bytes"] > 0
    _, _, narrow = choose_size(model, 4, 48, dev, sizes=sizes[1:], chunk=16)
    # a limit between the two sizes' needs selects the narrower one
    _with_limit(monkeypatch, (rep["need_bytes"] + narrow["need_bytes"]) // 2)
    slots, max_len, rep = choose_size(model, 4, 48, dev, **kw)
    assert (slots, max_len) == (4, 128)
    assert "4 slots x max_len 128" in describe(rep, 4)
    _with_limit(monkeypatch, 1)
    with pytest.raises(ValueError, match="no size"):
        choose_size(model, 4, 48, dev, **kw)
    # too long for every candidate, whatever the memory
    _with_limit(monkeypatch, 1 << 40)
    with pytest.raises(ValueError, match="no size"):
        choose_size(model, 4, 128, dev, **kw)


def test_choose_size_without_a_device_limit(smoke):
    """The CPU reports no memory limit: the widest size that holds the
    sequence is taken, and nothing is compiled for it."""
    _, model, _ = smoke
    slots, max_len, rep = choose_size(model, 4, 200, jax.devices()[0],
                                      sizes=((8, 128), (4, 256)), chunk=16)
    assert (slots, max_len) == (4, 256) and rep["bytes_limit"] is None
    assert "no memory limit" in describe(rep, 4)


def test_serve_phase_completes_and_matches_reference(smoke):
    cfg, model, params = smoke
    ecfg = cs.engine_config(4, 128, chunk=16)
    trace = _trace(cfg)
    res = cs.serve_phase(model, params, ecfg, trace)
    cs.check_complete(trace, res["outputs"])
    assert res["completed"] == len(trace) and res["decode_steps"] > 0
    assert res["prefill_tokens"] == sum(r.input_len for r, _ in trace)
    ref = cs.reference_check(model, params, trace, res["outputs"], ecfg.max_len)
    assert ref["first_token_match"] == len(trace)
    assert 0.0 < ref["token_agreement"] <= 1.0


def test_check_complete_rejects_a_short_request(smoke):
    cfg, model, params = smoke
    trace = _trace(cfg, n=1)
    res = cs.serve_phase(model, params, cs.engine_config(4, 128, chunk=16), trace)
    outputs = {rid: toks[:-1] for rid, toks in res["outputs"].items()}
    with pytest.raises(SystemExit, match="tokens="):
        cs.check_complete(trace, outputs)


def test_pallas_phase_interpreted_on_cpu(smoke):
    cfg, model, params = smoke
    ecfg = cs.engine_config(4, 128, chunk=16)
    pallas_model = build_model(cfg.replace(attn_impl="pallas"))
    chk = cs.pallas_checks(model, pallas_model, params, ecfg, _trace(cfg)[0][1])
    # interpreted on the CPU: no compiled kernel in the program
    assert chk["custom_call"] == {"chunk": False, "decode": False}
    assert chk["finite"] and chk["logit_rel_l2"] <= cs.PALLAS_LOGIT_RTOL
    trace = _trace(cfg)
    res = cs.serve_phase(pallas_model, params, ecfg, trace)
    cs.check_complete(trace, res["outputs"])


_FOUR = """
import json, sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke as cs
from repro.configs import get_config
from repro.models import build_model
cfg = get_config({arch!r})
model = build_model(cfg)
params = model.init(jax.random.key(0))
trace = lambda: cs.make_trace(cfg.vocab_size, 6, 0, (8, 40), (4, 8), gap=0.0)
rep = cs.four_chip_comparison(model, params, cs.engine_config(2, 64, chunk=16),
                              trace, jax.devices())
print(json.dumps(dict(rep, n_devices=len(jax.devices()))))
"""


def test_four_device_placement_matches_single_device():
    """The --chips 4 comparison on four virtual CPU devices: spread over
    four devices and all on device 0 give the same tokens and counts."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=_ROOT, arch=SMOKE)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rep["n_devices"] == 4 and rep["requests"] == 6
    for key in ("identical_tokens", "identical_handoff", "identical_deflection",
                "spread_placed", "single_placed", "all_done"):
        assert rep[key], key
    # the fleet really handed KV across servers, and deflected some prefills
    assert rep["handoff"]["cross_transfers"] > 0 and rep["deflected"] > 0
    assert rep["handoff"]["local_transfers"] == rep["deflected"]
    assert rep["handoff"]["transfers_completed"] == 6


def test_main_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
