"""The benchmark's yardstick on the CPU: traffic laws, metric arithmetic,
FLOP and byte counts, the cell files, and the refusals."""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402,F401  (puts the repo and src on sys.path)

from bench import counts, peaks, spec, stats, traffic  # noqa: E402
from bench.hooks import Served  # noqa: E402

ROOT = tiny.ROOT


# --------------------------------------------------------------------------- #
# copies of the program's laws
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("law", ["fixed:64", "uniform:16:128",
                                 "lognormal:256:0.8:768",
                                 "lognormal:192:0.5:512"])
def test_length_law_copy_draws_as_the_program(law):
    from repro.serve import traffic as prog
    a, b = random.Random(7), random.Random(7)
    mine, theirs = traffic.parse_length_dist(law), prog.parse_length_dist(law)
    assert [mine(a) for _ in range(500)] == [theirs(b) for _ in range(500)]


def test_gap_law_copy_draws_as_the_program():
    from repro.serve import traffic as prog
    assert traffic.poisson_arrivals(0.7, 300, random.Random(11)) == \
        prog.poisson_arrivals(0.7, 300, random.Random(11))
    assert traffic.poisson_arrivals(0.0, 3, random.Random(1)) == [0.0] * 3


def test_percentile_copy_matches_the_program():
    from repro.serve.metrics import percentile as prog
    rng = random.Random(3)
    for n in (1, 2, 9, 10, 11, 100):
        xs = [rng.random() for _ in range(n)]
        for p in (50, 90, 95, 99, 100):
            assert stats.percentile(xs, p) == prog(xs, p)


# --------------------------------------------------------------------------- #
# traffic: the same work for every seed, in another order
# --------------------------------------------------------------------------- #
def _mix(name):
    return traffic.load_mix(ROOT / "bench" / "traffic" / f"{name}.json")


def test_open_loop_offers_the_same_work_to_every_seed():
    """Sizes and due times are the mix's; the seed draws the tokens."""
    mix, cell = _mix("chat"), {"rate_per_s": 0.48}
    runs = [traffic.make_traffic(mix, cell, s, 151936, 50.0)
            for s in (1, 2**31 + 5)]
    shape = [[(len(i.prompt), i.max_new, i.due) for i in r] for r in runs]
    assert shape[0] == shape[1]
    assert [i.prompt for i in runs[0]] != [i.prompt for i in runs[1]]
    r = runs[0]
    assert len(r) > 10 and r[-1].due <= 50.0
    assert all(a.due <= b.due for a, b in zip(r, r[1:]))
    assert all(len(i.prompt) + i.max_new <= 1024 for i in r)
    assert traffic.make_traffic(mix, cell, 1, 151936, 50.0) == runs[0]


def test_the_gaps_are_the_mix_draw_of_the_copied_law():
    mix = _mix("chat")
    due = [i.due for i in traffic.make_traffic(mix, {"rate_per_s": 0.48}, 9,
                                               151936, 50.0)]
    law = traffic.poisson_arrivals(0.48, 200, random.Random(mix["shape_seed"]))
    assert due == pytest.approx([t for t in law if t <= 50.0])


def test_backlog_is_due_at_once():
    items = traffic.make_traffic(_mix("batch-decode"), {"backlog": 12}, 4,
                                 151936, 30.0)
    assert len(items) == 12 and {i.due for i in items} == {0.0}
    assert all(traffic.FIRST_TOKEN <= t < 151936 for i in items
               for t in i.prompt)


# --------------------------------------------------------------------------- #
# end-to-end arithmetic on synthetic timestamps
# --------------------------------------------------------------------------- #
def _served(due, times):
    r = Served("r", (1,), 4, due)
    r.token_times = list(times)
    return r


def test_ttft_censors_requests_without_a_first_token():
    reqs = [_served(1.0, [1.5, 2.0]),        # 0.5
            _served(2.0, []),                # none by t1=10: age 8.0
            _served(9.0, [10.5]),            # after t1: age 1.0
            _served(11.0, [11.1]),           # due after the window: out
            _served(-1.0, [0.5])]            # due before the window: out
    assert sorted(stats.ttft_samples(reqs, 0.0, 10.0)) == [0.5, 1.0, 8.0]
    assert stats.end_to_end("ttft_p90_s", reqs, 0.0, 10.0) == 8.0


def test_tokens_count_only_the_window():
    reqs = [_served(0.0, [-1.0, 1.0, 1.5, 3.0, 11.0]),
            _served(0.0, [2.0, 2.25])]
    assert stats.tokens_in(reqs, 0.0, 10.0) == 5
    assert stats.end_to_end("tokens_per_s", reqs, 0.0, 10.0) == 0.5
    assert stats.end_to_end("tokens_per_s", [], 0.0, 10.0) is None
    with pytest.raises(KeyError):
        stats.end_to_end("itl_p95_ms", reqs, 0.0, 10.0)


def test_itl_counts_gaps_with_both_tokens_in_the_window():
    reqs = [_served(0.0, [-0.5, 0.5, 1.0, 3.0, 10.5]),   # 1.0-0.5, 3.0-1.0
            _served(0.0, [2.0, 2.25]),                  # 0.25
            _served(0.0, [4.0])]                        # no gap
    assert sorted(stats.itl_samples(reqs, 0.0, 10.0)) == [0.25, 0.5, 2.0]
    assert stats.end_to_end("itl_p99_ms", reqs, 0.0, 10.0) == 2000.0
    assert stats.end_to_end("itl_p99_ms", reqs[2:], 0.0, 10.0) is None


# --------------------------------------------------------------------------- #
# FLOP and byte counts against hand sums
# --------------------------------------------------------------------------- #
def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_counts_qwen2_by_hand():
    c = _config("qwen2-1.5b")
    per_layer = 1536 * 128 * (2 * 12 + 2 * 2) + 3 * 1536 * 8960
    assert counts.matmul_params(c) == 28 * per_layer + 1536 * 151936
    assert counts.kv_bytes_per_position(c) == 28672
    # one token attending 100 positions: 2 FLOPs per weight, QK and PV
    assert counts.token_flops(c, 100) == 2 * counts.matmul_params(c) \
        + 4 * 28 * 12 * 128 * 100
    by = counts.decode_bytes(c, (100, 200), param_bytes=6e9)
    assert by == 6e9 + (300 + 2) * 28672


#: phi3-mini-3.8b (HF microsoft/Phi-3-mini-4k-instruct), MHA, untied head.
PHI3 = {"hidden_size": 3072, "intermediate_size": 8192,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 32, "vocab_size": 32064}


def test_counts_phi3_by_hand():
    c = PHI3
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert counts.matmul_params(c) == 32 * per_layer + 3072 * 32064
    assert counts.kv_bytes_per_position(c) == 393216
    by = counts.decode_bytes(c, (10,), param_bytes=3.8e9, chips=4)
    assert by == 3.8e9 + 11 * 393216 / 4


def test_least_time_takes_the_larger_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = counts.least_time(197e12, 819e9 * 2, pk)
    assert (t, bound) == (2.0, "memory")
    t, bound = counts.least_time(197e12 * 8, 819e9, pk, chips=4)
    assert (t, bound) == (2.0, "compute")


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# --------------------------------------------------------------------------- #
# BENCHMARK.json resolves to its files
# --------------------------------------------------------------------------- #
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    from bench.serve import program_config
    cell = spec.load_cell(name)
    cfg = program_config(cell.config)          # sizes as the program runs
    assert cfg.name == cell.config["program_arch"]
    assert cell.cell["slots"] >= 1 and cell.cell["max_seq"] % 16 == 0
    assert "logit_gap" in cell.cell["check"]["limits"]
    if cell.mix["arrivals"] == "poisson":
        assert cell.cell["rate_per_s"] > 0
    else:
        assert cell.cell["backlog"] > cell.cell["slots"]
    assert cell.chips == cell.cell.get("model_parallel", 1)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in names, (m["name"], name)


def test_a_backlog_outlasts_the_window_at_the_roofline():
    """An offline cell's backlog holds more tokens than its slots could
    decode in a window even at the least time of a step with bfloat16
    weights and no live K/V, so a faster program never drains it."""
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        if cell.mix["arrivals"] != "backlog":
            continue
        c, slots = cell.config, cell.cell["slots"]
        least, _ = counts.least_time(
            counts.decode_flops(c, (0,) * slots),
            counts.decode_bytes(c, (), 2 * counts.matmul_params(c)),
            peaks.peaks_for("TPU v5 lite"), cell.chips)
        items = traffic.make_traffic(cell.mix, cell.cell, 1, 100, 1.0)
        backlog_tokens = sum(it.max_new for it in items)
        assert backlog_tokens > slots / least * BENCH["run_seconds"]


def test_every_config_and_traffic_is_used_and_present():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


# --------------------------------------------------------------------------- #
# refusals
# --------------------------------------------------------------------------- #
def test_a_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=str(ROOT))
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_a_copy_without_the_program_exits_nonzero(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
