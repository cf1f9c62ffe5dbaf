"""The traffic generator, and the by-name lookup of cells' files."""
import json
import shutil
from collections import Counter

import numpy as np
import pytest

from perf import generate, harness, model
from perf.tests import tiny

SEEDS = [0, 1, 2**31 + 11, 2**33 + 7]
counts = generate.generator({"generator": "sized"}).counts


def _take(spec, seed, n, vocab=1000):
    draw = generate.requests(spec, seed, vocab)
    return [draw(i % spec.get("clients", 1)) for i in range(n)]


@pytest.mark.parametrize("name", ["codegen_closed32", "chat_poisson"])
def test_same_seed_same_requests(name):
    spec = generate.load(name)
    a, b = _take(spec, 5, 60), _take(spec, 5, 60)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_tokens, x.arrival_s) == (y.max_tokens, y.arrival_s)
    c = _take(spec, 6, 60)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["codegen_closed32", "chat_poisson"])
@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_draws_the_fixed_shapes(name, seed):
    spec = generate.load(name)
    block = spec["block"]
    reqs = _take(spec, seed, 5 * block)
    # a closed loop's first requests are cut to their residual length
    skip = -(-spec.get("clients", 0) // block) if spec["loop"] == "closed" \
        else 0
    for b in range(skip, 5):
        chunk = reqs[b * block:(b + 1) * block]
        plens = Counter(len(r.prompt) for r in chunk)
        olens = Counter(r.max_tokens for r in chunk)
        for key, got in (("prompt_len", plens), ("output_len", olens)):
            want = dict(zip(spec[key]["values"],
                            counts(spec[key], block)))
            assert dict(got) == want
    for b in range(skip):
        chunk = reqs[b * block:(b + 1) * block]
        plens = Counter(len(r.prompt) for r in chunk)
        assert dict(plens) == dict(zip(
            spec["prompt_len"]["values"],
            counts(spec["prompt_len"], block)))
    assert set(len(r.prompt) for r in reqs) <= \
        set(generate.shapes(spec)["prompt_len"])
    assert max(r.max_tokens for r in reqs) <= \
        max(generate.shapes(spec)["output_len"])


def test_closed_loop_starts_part_way():
    spec = generate.load("codegen_closed32")
    n = spec["clients"]
    firsts = [_take(spec, seed, n) for seed in SEEDS[:2]]
    for reqs in firsts:
        assert all(2 <= r.max_tokens for r in reqs)
        assert sum(r.max_tokens for r in reqs) < \
            n * generate.mean_request(spec)["output_len"]


def test_open_loop_gaps_are_one_multiset_per_block():
    spec = generate.load("chat_poisson")
    n = spec["arrival_block"]
    per_seed = []
    for seed in SEEDS[:3]:
        reqs = _take(spec, seed, n + 1)
        gaps = np.diff([r.arrival_s for r in reqs])
        per_seed.append(np.sort(gaps))
    for g in per_seed[1:]:
        np.testing.assert_allclose(g, per_seed[0])
    assert per_seed[0].mean() == pytest.approx(1 / spec["rate_per_s"],
                                               rel=0.05)


def test_weights_must_fill_a_block():
    with pytest.raises(ValueError):
        counts({"values": [1, 2], "weights": [0.33, 0.67]}, 20)


@pytest.mark.parametrize("seed", SEEDS)
def test_shared_prefixes_stay_with_their_clients(seed):
    spec = tiny.ROUTER
    groups, plen = spec["prefix"]["groups"], spec["prefix"]["len"]
    n = spec["clients"]
    reqs = _take(spec, seed, 4 * n)
    prefixes = {}
    for i, r in enumerate(reqs):
        head = tuple(r.prompt[:plen])
        assert prefixes.setdefault((i % n) % groups, head) == head
        assert len(r.prompt) - plen in spec["prompt_len"]["values"]
    assert len(set(prefixes.values())) == groups
    again = _take(spec, seed, 4 * n)
    assert all(np.array_equal(a.prompt, b.prompt)
               for a, b in zip(reqs, again))


def test_warm_up_reaches_every_prompt_and_tail():
    spec = tiny.ROUTER
    rounds = generate.warm_up(spec, 100, np.random.default_rng(0))
    plen = spec["prefix"]["len"]
    cold = sorted(len(r.prompt) for r in rounds[0])
    assert cold == generate.shapes(spec)["prompt_len"]
    hit = rounds[1]
    assert len({tuple(r.prompt[:plen]) for r in hit}) == 1
    assert sorted({len(r.prompt) - plen for r in hit[1:]}) == \
        generate.shapes(spec)["tail_len"]


def test_metric_names_kept_apart_per_cell_read_as_their_quantity():
    known = {"itl_p50_ms", "step.decode_ms", "ttft_p95_ms"}
    assert harness.by_name("itl_p50_ms.chat", known) == "itl_p50_ms"
    assert harness.by_name("step.decode_ms.codegen", known) == \
        "step.decode_ms"
    assert harness.by_name("step.decode_ms", known) == "step.decode_ms"
    assert harness.by_name("ttft_p50_ms.chat", known) is None


def test_files_dropped_in_are_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "arch").mkdir()
    mix = dict(generate.load("codegen_closed32"), clients=3)
    (tmp_path / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    shutil.copy(generate.TRAFFIC_DIR / "sized.py", tmp_path / "traffic")
    (tmp_path / "configs" / "new_cfg.json").write_text(json.dumps(
        {"name": "new_cfg", "arch": "new_arch", "hidden_size": 1}))
    (tmp_path / "arch" / "new_arch.py").write_text(
        "def check(spec):\n    pass\n")
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return run.value * 2\n")
    (tmp_path / "traffic" / "fixed.py").write_text(
        "def check(spec):\n    pass\n"
        "def requests(spec, seed, vocab):\n"
        "    return lambda client: (seed, client)\n")
    (tmp_path / "traffic" / "other_mix.json").write_text(
        json.dumps({"generator": "fixed"}))
    monkeypatch.setattr(generate, "TRAFFIC_DIR", tmp_path / "traffic")
    monkeypatch.setattr(model, "CONFIG_DIR", tmp_path / "configs")
    monkeypatch.setattr(model, "ARCH_DIR", tmp_path / "arch")
    monkeypatch.setattr(harness, "METRICS_DIR", tmp_path / "metrics")
    assert generate.load("new_mix")["clients"] == 3
    assert model.load("new_cfg")["hidden_size"] == 1
    assert model.arch(model.load("new_cfg")).__file__ == str(
        tmp_path / "arch" / "new_arch.py")
    assert harness.reader("new.metric")(harness.RunData(value=4)) == 8
    assert harness.reader("new.metric.cell")(harness.RunData(value=1)) == 2
    other = generate.load("other_mix")
    assert generate.requests(other, 3, 10)(5) == (3, 5)


def test_every_named_file_exists():
    bench = json.loads((harness.METRICS_DIR.parents[1]
                        / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert model.load(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        generate.load(w["traffic"])
    for m in bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
