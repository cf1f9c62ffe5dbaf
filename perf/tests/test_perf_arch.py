"""The seam between a configuration and its architecture module.

* The dense decoder behind ``perf/arch/dense.py`` makes the same weights,
  reference logits and work counts, bit for bit, as it did before it
  moved there (digests recorded from that code).
* A second architecture, added as a file beside ``dense.py`` with no
  edit to any file under ``perf/``, is served, made and checked end to
  end by the benchmark's own run; the dense reference does not pass it.
* A configuration that names no architecture, or a missing one, fails
  when it is loaded, naming the file.
"""
import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from perf import harness, model, work
from perf.tests import tiny

HERE = Path(__file__).resolve().parent

# sha256 digests of the dense decoder's weights (tiny.SPEC, seed 3), its
# reference logits over one sequence and their fp8 control, and its work
# counts on tiny, dsc33b_6l and danube3_4b at positions 0, 1, 255, 2047
DIGESTS = {
    "params": "728157baa8c4bc310c63a431b9489376ae492233e1b8219f3a42b28932e718ca",
    "logits": "183ec051118c452babb25d3bb3a54c1724c2beec14fc9b30fd1607b71577e503",
    "logits_fp8":
        "9b05ef1e9e2cdacebf713b8b8d79f0a5173c4ea589dabcdca9ab566a22fd3d7c",
    "counts": "bc0ad570543ea89c4f83dc98a2b5d913a0d5c6f84ec84269b34f544b8936805b",
}

# the program's parallel-block decoder at tiny's sizes, under the
# test-only architecture ``perf/tests/arch/parallel.py``
PARALLEL = dict(tiny.SPEC, name="tiny_parallel", arch="parallel",
                overrides=dict(tiny.SPEC["overrides"], parallel_block=True))


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _tree_sha(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def dense_made():
    spec = tiny.SPEC
    dense = model.arch(spec)
    params = dense.make_params(spec, model.program_config(spec), seed=3,
                               device=jax.devices()[0])
    ref = dense.Reference(spec, params, 48)
    seq = np.random.default_rng(0).integers(0, spec["vocab_size"], 37)
    counts = []
    for s in (spec, model.load("dsc33b_6l"), model.load("danube3_4b")):
        for pos in (0, 1, 255, 2047):
            counts.append([work.prompt_flops(s, pos + 1),
                           work.decode_flops(s, pos),
                           list(work.paged_attention_work(s, pos))])
    return {"params": _tree_sha(params),
            "logits": _sha(np.asarray(ref.logits(seq)).tobytes()),
            "logits_fp8": _sha(np.asarray(ref.logits(seq, fp8=True))
                               .tobytes()),
            "counts": _sha(json.dumps(counts).encode())}


@pytest.mark.parametrize("what", sorted(DIGESTS))
def test_dense_module_is_bit_equal_to_before(dense_made, what):
    assert dense_made[what] == DIGESTS[what]


@pytest.fixture(scope="module")
def arch_dir(tmp_path_factory):
    """A directory of architectures: ``dense.py`` as it is, and the
    test-only ``parallel.py`` added beside it; the loader reads it."""
    d = tmp_path_factory.mktemp("arch")
    shutil.copy(model.ARCH_DIR / "dense.py", d)
    shutil.copy(HERE / "arch" / "parallel.py", d)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(model, "ARCH_DIR", d)
        yield d


def _run(spec, trace=False, bench=None):
    return harness.run(bench or tiny.bench(), tiny.CELL, spec,
                       tiny.traffic(), seed=2**33 + 17, seconds=2.0,
                       trace=trace, t_proc=time.monotonic(),
                       require_tpu=False, kind="TPU v5 lite",
                       log=lambda m: None)


def test_a_second_architecture_runs_end_to_end(arch_dir):
    bench = {"end_to_end": [], "per_layer": [
        {"name": "mfu", "unit": "%"},
        {"name": "kv.pool_peak_share", "unit": "%"}]}
    out = _run(PARALLEL, trace=True, bench=bench)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0
    # the work counts reach the reader through the new module
    assert out["metrics"]["mfu"]["value"] > 0
    assert work.decode_flops(PARALLEL, 9) == \
        model.arch(tiny.SPEC).decode_flops(tiny.SPEC, 9)


@pytest.fixture(scope="module")
def parallel_params(arch_dir):
    return model.arch(PARALLEL).make_params(
        PARALLEL, model.program_config(PARALLEL), seed=3,
        device=jax.devices()[0])


def test_the_parallel_reference_matches_the_program_forward(
        arch_dir, parallel_params):
    from perf.tests.test_perf_reference import _program_logits
    params = parallel_params
    assert "norm2" not in params["layers"]
    seq = np.random.default_rng(1).integers(0, PARALLEL["vocab_size"], 29)
    want = _program_logits(PARALLEL, params, seq)
    got = np.asarray(model.arch(PARALLEL).Reference(PARALLEL, params, 32)
                     .logits(seq))[:29]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_the_dense_reference_cannot_read_parallel_weights(parallel_params):
    with pytest.raises(KeyError, match="norm2"):
        model.arch(tiny.SPEC).Reference(tiny.SPEC, parallel_params,
                                        32).logits(np.arange(8))


def test_the_dense_reference_fails_a_parallel_program(arch_dir):
    """Dense weights served by the parallel-block program (which never
    reads ``norm2``), judged by the reference ``"arch": "dense"`` names:
    not correct."""
    out = _run(dict(PARALLEL, arch="dense"))
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("arch, missing", [
    (None, "no \"arch\" key"), ("no_such_arch", "no_such_arch.py")],
    ids=["no_arch_key", "missing_module"])
def test_a_configuration_fails_at_load_naming_the_file(tmp_path, monkeypatch,
                                                       arch, missing):
    spec = model.load("dsc33b_6l")
    spec.pop("arch")
    if arch:
        spec["arch"] = arch
    (tmp_path / "broken.json").write_text(json.dumps(spec))
    monkeypatch.setattr(model, "CONFIG_DIR", tmp_path)
    with pytest.raises(ValueError) as exc:
        model.load("broken")
    assert str(tmp_path / "broken.json") in str(exc.value)
    assert missing in str(exc.value)


def test_a_configuration_missing_a_size_fails_at_load(tmp_path,
                                                      monkeypatch):
    spec = model.load("danube3_4b")
    spec.pop("head_dim")
    (tmp_path / "short.json").write_text(json.dumps(spec))
    monkeypatch.setattr(model, "CONFIG_DIR", tmp_path)
    with pytest.raises(ValueError, match="head_dim"):
        model.load("short")
