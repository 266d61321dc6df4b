"""The benchmark's own arithmetic against hand counts: FLOPs and bytes
per configuration, the traffic generators' repeatability, the window's
percentiles, and the peak table."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, model, serve, traffic  # noqa: E402

KIMI = model.dims(model.load_config("kimi-k2-gqa-e16"))
ARCTIC = model.dims(model.load_config("arctic-e8"))
KIMI_ARCH = model.arch(model.load_config("kimi-k2-gqa-e16"))
ARCTIC_ARCH = model.arch(model.load_config("arctic-e8"))


def test_kimi_token_flops_hand_count():
    d = 7168
    layer = (2 * d * 128 * (64 * 2 + 8 * 2)     # q, k, v, o projections
             + 2 * 2 * 64 * 128 * 1000          # scores and values, ctx 1000
             + 2 * d * 16                       # router
             + 8 * 2 * 3 * d * 2048)            # 8 SwiGLU experts
    assert layer == 1_001_881_600
    total = 4 * layer + 2 * d * 20480           # 4 layers + unembedding
    assert KIMI_ARCH.token_flops(KIMI, 1000) == total == 4_301_127_680


def test_arctic_train_flops_hand_count():
    d = 7168
    layer = (2 * d * 128 * (56 * 2 + 8 * 2)
             + 2 * 2 * 56 * 128 * 2048.5        # causal: (4096 + 1) / 2
             + 2 * d * 8
             + 2 * 2 * 3 * d * 4864             # top-2 experts
             + 2 * 3 * d * 7168)                # parallel dense FFN
    per_token = layer + 2 * d * 32000
    assert flops.train_flops(ARCTIC_ARCH, ARCTIC, 4096, 8192) == \
        pytest.approx(3 * 8192 * per_token, rel=1e-12)
    assert 3 * per_token == pytest.approx(4.4374e9, rel=1e-4)


def test_gmm_and_dispatch_bytes_hand_count():
    d, f = 7168, 2048
    assert flops.gmm_flops(KIMI, 256) == 2 * 3 * 256 * d * f
    assert flops.gmm_bytes(KIMI, 256, 16) == 2 * (3 * d * f * 16
                                                  + 2 * d * 256)
    assert flops.dispatch_combine_bytes(KIMI, 32, 256) == \
        2 * d * 2 * (32 + 256)
    peak = flops.peaks("TPU v5 lite")
    # 16 experts' weights dominate a decode call: bandwidth-bound
    t = flops.least_time(flops.gmm_flops(KIMI, 256),
                         flops.gmm_bytes(KIMI, 256, 16), peak)
    assert t == pytest.approx(flops.gmm_bytes(KIMI, 256, 16) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def _cell(name):
    with open(os.path.join(ROOT, "bench", "workloads", f"{name}.json")) as f:
        return json.load(f)


def test_requests_repeat_for_a_seed_and_differ_for_another():
    t = _cell("kimi-e16.chat-poisson")["traffic"]
    big = 2 ** 31 + 12345
    a = traffic.requests(t, 20480, big)
    b = traffic.requests(t, 20480, big)
    c = traffic.requests(t, 20480, big + 1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.out_len == y.out_len
               for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # every seed serves the same multiset of sizes, in its own order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                       for x in c)
    assert sorted(x.out_len for x in a) == sorted(x.out_len for x in c)
    lens = np.array([len(x.prompt) for x in a])
    assert lens.min() >= 64 and lens.max() <= 4096
    assert np.median(lens) == pytest.approx(1020, abs=30)


def test_poisson_arrivals_repeat_and_keep_the_rate():
    t = dict(_cell("kimi-e16.chat-poisson")["traffic"],
             arrivals={"process": "poisson", "rate": 4.0})
    a = traffic.requests(t, 1000, 7)
    b = traffic.requests(t, 1000, 7)
    c = traffic.requests(t, 1000, 8)
    assert [x.arrival for x in a] == [x.arrival for x in b]
    assert [x.arrival for x in a] != [x.arrival for x in c]
    n = len(a)
    assert a[-1].arrival == pytest.approx(c[-1].arrival)   # same gaps
    assert n / a[-1].arrival == pytest.approx(4.0, rel=0.05)


def test_training_batches_copy_the_program_pipeline():
    from repro.data.pipeline import DataConfig, batch_at
    seed = traffic.data_seed(2 ** 31 + 99)
    dc = DataConfig(vocab_size=32000, seq_len=64, batch_size=2,
                    n_clusters=64, noise_prob=0.05, seed=seed)
    for step in (0, 1, 2):
        ours = traffic.batch_at(32000, 64, 2, 64, 0.05, seed, step)
        theirs = batch_at(dc, step)
        assert np.array_equal(ours["tokens"], np.asarray(theirs["tokens"]))
        assert np.array_equal(ours["labels"], np.asarray(theirs["labels"]))
    a = traffic.batch_at(32000, 64, 2, 64, 0.05, seed, 0)
    b = traffic.batch_at(32000, 64, 2, 64, 0.05, seed + 1, 0)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_window_metrics_tails_over_all_requests():
    # window [0, 10]; 20 requests due at t, first token 0.1 s later,
    # then tokens every 0.05 s; one step stalls 2 s for request 0.
    reqs = []
    for i in range(20):
        due = 0.2 * i
        times = [due + 0.1 + 0.05 * j for j in range(5)]
        reqs.append((due, times))
    reqs[0] = (0.0, [0.1, 0.15, 2.15, 2.2, 2.25])
    # a request due in the window, first token after it: late, counted
    reqs.append((9.9, [10.5, 10.55]))
    # a request sent before the window (in the ramp): its
    # tokens in the window count, its first-token time does not
    reqs.append((-1.0, [-0.5, 0.5, 0.55]))
    met, med, failed = serve.window_metrics(reqs, 0.0, 10.0)
    assert failed == 0
    assert med["requests_due"] == 21
    ttft = sorted([100.0] * 20 + [600.0])
    assert met["ttft_p95_ms"] == pytest.approx(np.percentile(ttft, 95))
    assert met["ttft_p95_ms"] > 100.0           # the late one is in the tail
    gaps = [50.0] * 80 + [1000.0, 50.0]         # + the ramp request's
    gaps[0] = 2000.0                            # the stalled step
    assert met["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95))
    assert max(gaps) == 2000.0 and med["gaps"] == 82
    assert met["out_tok_s"] == pytest.approx(102 / 10.0)
    # a request due in the window that never gets a token fails
    _, _, failed = serve.window_metrics(reqs + [(5.0, [])], 0.0, 10.0)
    assert failed == 1


def test_percentile_is_over_all_values():
    v = list(range(1, 101))
    assert traffic.percentile(v, 95) == pytest.approx(95.05)
    assert np.isnan(traffic.percentile([], 95))
