"""The seam between the harness and a model: a configuration names its
architecture, a module found by name; the dense architecture gives what
the benchmark gave before it had the seam.

The constants below were recorded from the benchmark as it stood before the
seam, on this x86 CPU (seed 3000000007): a hash of the drawn leaves, of the
reference's outputs (float32 bytes) and the work counts. Equal hashes mean
bit-identical weights and reference, so the cells that exist read what they
read before."""
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import modelspec
import reference
import weights
from conftest import BENCH, HERE

FIXTURES = HERE / "fixtures"
SEED = 3000000007
SEL = [[0, 0], [0, 3], [0, 7]]
PROMPTS, SERVED = [[3, 7, 11], [5, 6]], [[1, 2, 3, 4], [9, 9]]


def digest(tree):
    m = hashlib.sha256()
    for k in sorted(tree):
        m.update(k.encode())
        m.update(np.ascontiguousarray(np.asarray(tree[k])).tobytes())
    return m.hexdigest()[:16]


def flat(tree, pre=""):
    out = {}
    for k, v in tree.items():
        out.update(flat(v, f"{pre}{k}/") if isinstance(v, dict) else {pre + k: v})
    return out


def full_width(name):
    """The configuration, and layer 0 with a seeded input of 8 rows."""
    _, arch, s = harness.load_config(BENCH, name)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 8, s.d)), jnp.float32)
    return arch, s, x


WEIGHTS = {
    ("minicpm-2b", "layer1"): "98cb5082151d1197", ("minicpm-2b", "top"): "bedaac39c7be573d",
    ("mistral-7b-v0.3-16l", "layer1"): "d09e31b2dc5d9cda",
    ("mistral-7b-v0.3-16l", "top"): "fb857dc4dc5a45aa",
}


@pytest.mark.parametrize("name, what", list(WEIGHTS), ids=["-".join(k) for k in WEIGHTS])
def test_dense_weights_are_the_parents(name, what):
    _, arch, s = harness.load_config(BENCH, name)
    if what == "layer1":
        got = weights.layer_params(arch, s, SEED, 1)
    else:  # the embedding apart: the head and the final norm
        got = {k: v for k, v in weights.top_params(arch, s, SEED).items() if k != "embed"}
    assert digest(got) == WEIGHTS[name, what]


REFERENCE = {
    ("minicpm-2b", None): ("f5f3f6b6e0cf86b8", "96b31f9f30f13eaa"),
    ("minicpm-2b", "int8"): ("37a9c7031333db20", "3604153b6a2185e5"),
    ("mistral-7b-v0.3-16l", None): ("6dcd4fd123857057", "ebbf56d2eaf195fb"),
    ("mistral-7b-v0.3-16l", "int8"): ("23e53bed664360e2", "f071f07c19400dca"),
}


@pytest.mark.parametrize("name, quant", list(REFERENCE), ids=[f"{n}-{q}" for n, q in REFERENCE])
def test_dense_reference_is_the_parents_at_full_width(name, quant):
    arch, s, x = full_width(name)
    layer = arch.reference_layer(s, 0, x, weights.layer_params(arch, s, SEED, 0), quant)
    head = arch.reference_head(s, x, jnp.asarray(SEL, jnp.int32), weights.top_params(arch, s, SEED),
                               quant)
    assert (digest({"y": layer}), digest({"y": head})) == REFERENCE[name, quant]


FIXTURE_REFERENCE = {
    "tiny": dict(params="aac0102666762d7d", logits="145b10d67404529b",
                 logits_int8="56c4f5add5803998", score="3ff4baa10909b0dc",
                 control=[[59, 59, 169, 137], [104, 245]]),
    "small": dict(params="487616ce98ee2ca6", logits="d960df4c5c15dffb",
                  logits_int8="3066dd4018d5bdef", score="07f34d9be22b7396",
                  control=[[2877, 2375, 387, 2223], [3135, 2932]]),
}


@pytest.mark.parametrize("name", list(FIXTURE_REFERENCE))
def test_dense_whole_model_is_the_parents(name):
    """Every weight, and the reference's logits, score and control tokens
    through the whole model, at the dense fixtures' sizes."""
    want = FIXTURE_REFERENCE[name]
    _, arch, s = harness.load_config(FIXTURES, name)
    assert digest(flat(weights.make_params(arch, s, SEED))) == want["params"]
    seqs, pos = [[3, 7, 11, 200 % s.vocab, 5, 9], list(range(2, 40))], [[2, 5], [0, 17, 37]]
    for quant, key in ((None, "logits"), ("int8", "logits_int8")):
        lg = reference.logits_at(arch, s, SEED, seqs, pos, quant)
        assert digest({str(i): a for i, a in enumerate(lg)}) == want[key]
    assert digest({"s": reference.score(arch, s, SEED, PROMPTS, SERVED)}) == want["score"]
    assert reference.control_tokens(arch, s, SEED, PROMPTS, SERVED) == want["control"]


WORK = {
    "minicpm-2b": [(1262930563584, 5545805316), (1066295038464, 6182146816),
                   (35536310784, 5563457568), (5451600384, 5452469260),
                   (23146039296, 6800229424)],
    "mistral-7b-v0.3-16l": [(1795598319616, 7267034112), (1427850133504, 7379682044),
                            (49681530880, 7268335644), (7249330176, 7248560136),
                            (29949952000, 7488315424)],
}


@pytest.mark.parametrize("name", list(WORK))
def test_dense_work_is_the_parents(name):
    _, arch, s = harness.load_config(BENCH, name)
    got = [arch.chunk_work(s, 0, 256), arch.chunk_work(s, 1792, 191), arch.chunk_work(s, 300, 7),
           arch.decode_work(s, [5]), arch.decode_work(s, [99, 9, 1500, 2046])]
    assert got == WORK[name]


def test_arch_is_found_by_name_beside_the_cells_first(tmp_path):
    conf, arch, _ = harness.load_config(BENCH, "minicpm-2b")
    assert "arch" not in conf and arch is modelspec.arch("dense")
    assert harness.load_config(FIXTURES, "moe-tiny")[1] is modelspec.arch("moe", FIXTURES)
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "dense.py").write_text('MARK = "beside the cells"\n')
    assert modelspec.arch("dense", tmp_path).MARK == "beside the cells"
    assert not hasattr(modelspec.arch("dense"), "MARK")


def test_harness_names_no_configuration_or_architecture():
    """Only configs/, cells/, traffic/ and archs/ know the models; the loader
    names the default architecture once."""
    names = [p.stem for p in (BENCH / "configs").glob("*.json")]
    names += [p.stem for p in (BENCH / "archs").glob("*.py")]
    files = [p for p in [*BENCH.glob("*.py"), *(BENCH / "layer_metrics").glob("*.py")]
             if p.name != "modelspec.py"]
    assert files
    for p in files:
        text = p.read_text()
        assert not [n for n in names if n in text], p
    assert modelspec.DEFAULT_ARCH in names
