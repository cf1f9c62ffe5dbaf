"""CPU rehearsal of ``chip_smoke.py``: its kernel-parity, serving and
reference-margin phases at ``paper_demo.reduced()`` widths with the
Pallas kernels in interpret mode, and its refusal to run off a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.kernels.impl import use_impl
from repro.models import lm

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_b_to_d_pass_on_cpu_interpret(smoke):
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    geometry = dict(page_size=16, max_seq_len=128)
    with use_impl("pallas_interpret"):
        errs = smoke.check_kernels(cfg, impl="pallas_interpret", seed=0,
                                   **geometry)
        assert set(errs) == {"paged_decode", "paged_suffix", "rmsnorm_13",
                             "rmsnorm_1009"}
        prompts = smoke.make_prompts(cfg.vocab_size, 0, n=6,
                                     lengths=(13, 20, 37))
        assert sorted({len(p) for p in prompts}) == [13, 20, 37]
        res = smoke.serve_phase(cfg, params, prompts, max_tokens=8,
                                **geometry)
    assert res["tokens"] == 6 * 8
    assert res["prefix_hits"] >= 1 and res["suffix_steps"] >= 1
    margins = smoke.reference_margins(cfg, params, prompts[:3],
                                      res["outputs"][:3])
    assert len(margins) == 3
    smoke.check_margins(margins, "cpu rehearsal")


def test_margin_rule_rejects_a_wrong_token(smoke):
    """A served stream with one token swapped for the reference's least
    likely token fails the margin rule."""
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = smoke.make_prompts(cfg.vocab_size, 1, n=3)[2]
    logits = lm.lm_forward(params, {"tokens": jax.numpy.asarray(prompt)[None]},
                           cfg)[0, -1]
    worst = int(jax.numpy.argmin(logits))
    margins = smoke.reference_margins(cfg, params, [prompt], [[worst]])
    with pytest.raises(smoke.SmokeFailure, match="trails"):
        smoke.check_margins(margins, "swapped token")


def test_main_refuses_a_non_tpu_platform(smoke, capsys, monkeypatch):
    # keep the entry point's cache placement out of the test process
    monkeypatch.setattr("repro.compile_cache.configure", lambda: "off")
    rc = smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in err and jax.devices()[0].platform in err
    assert '"ok"' not in out
