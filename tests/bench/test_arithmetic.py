"""The benchmark's own arithmetic: operation counts from widths, latency
percentiles over all requests, tokens per engine step, the peaks table
and the inputs drawn from a seed."""
from __future__ import annotations

import json
import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import device, flops, manifest as mf, stats, traffic
from smoke_cells import DATA, config_file


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


def test_deepseek_layer_and_step_flops_by_hand(man):
    cfg = mf.load_config(man, "deepseek-7b-tp4")
    # q, k, v, o: 4 x 4096 x 4096 (MHA); gate, up, down: 3 x 4096 x 11008
    assert flops.layer_matmul_params(cfg) == 67_108_864 + 135_266_304
    assert flops.head_params(cfg) == 4096 * 102_400
    tokens = 4 * 4096
    weights = 4 * 202_375_168 + 419_430_400
    # causal pairs of one 4096-token sequence: 4096 * 4097 / 2
    attn = 3 * (4 * 32 * 128 * 4) * 4 * 8_390_656
    assert flops.train_step_flops(cfg, 4, 4096) == 6 * weights * tokens + attn
    assert flops.train_step_flops(cfg, 4, 4096) == pytest.approx(127.4e12,
                                                                 rel=1e-3)


def test_internlm2_layer_and_step_flops_by_hand():
    full = config_file("internlm2-1.8b")
    cfg = dict(full, num_hidden_layers=4)
    # q, o: 2048 x 2048; k, v: 2048 x (8 x 128); gate, up, down: 2048 x 8192
    assert flops.layer_matmul_params(cfg) == (2 * 4_194_304 + 2 * 2_097_152
                                              + 3 * 16_777_216)
    step = flops.train_step_flops(cfg, 1, 4096)
    assert step == pytest.approx(11.67e12, rel=2e-3)
    # one request, 3-token prompt, 2 output tokens: 4 fed tokens
    one = flops.serve_flops(full, [(3, 2)])
    layer = 2 * 24 * flops.layer_matmul_params(full) * 4
    attn = 4 * 16 * 128 * 24 * (4 * 5 // 2)
    assert one == layer + attn + 2 * 2 * 2048 * 92_544


def test_percentiles_count_missing_requests():
    assert stats.nearest_rank([3.0, 1.0, 2.0, 4.0], 50) == 2.0
    assert stats.nearest_rank([3.0, 1.0, 2.0, 4.0], 90) == 4.0
    # one of ten never finished: p90 is the ninth value, p95 the missing one
    vals = [float(i) for i in range(1, 10)] + [stats.MISSING]
    assert stats.nearest_rank(vals, 90) == 9.0
    assert math.isinf(stats.nearest_rank(vals, 95))
    reqs = [SimpleNamespace(done=True, t_first_token=1.5, arrival_time=1.0,
                            token_times=[1.5, 1.6]),
            SimpleNamespace(done=False, t_first_token=None, arrival_time=2.0,
                            token_times=[])]
    assert stats.ttfts(reqs) == [0.5, stats.MISSING]
    gaps = stats.inter_token_gaps(reqs)
    assert len(gaps) == 1 and gaps[0] == pytest.approx(0.1)


def test_tokens_per_step_from_stamps():
    r = lambda ts: SimpleNamespace(token_times=ts)
    # steps at t=1 (2 tokens), t=2 (3 tokens), t=3 (1 token)
    reqs = [r([1.0, 2.0, 3.0]), r([1.0, 2.0]), r([2.0])]
    assert stats.tokens_per_step(reqs) == pytest.approx(6 / 3)
    assert stats.tokens_per_step([r([])]) is None


def test_unknown_device_kind_raises():
    assert device.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99")
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    with pytest.raises(device.NoChip):
        device.require_chips([cpu], 1)
    odd = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(device.NoChip):
        device.require_chips([odd], 1)
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(device.NoChip):
        device.require_chips([v5e], 4)


def test_every_seed_offers_the_same_work():
    tr = json.loads((DATA / "serve-smoke.json").read_text())
    a = traffic.serve_requests(tr, 7, 3.0, 92_544)
    b = traffic.serve_requests(tr, 2 ** 31 + 99, 3.0, 92_544)
    assert len(a) == len(b) > 10
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert max(x.arrival for x in a) < 3.0
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    lens = [len(x.prompt) for x in a]
    assert min(lens) >= 8 and max(lens) <= 40
    c = traffic.serve_requests(tr, 7, 3.0, 92_544)
    assert all((x.prompt == y.prompt).all() and x.arrival == y.arrival
               for x, y in zip(a, c))


def test_train_tokens_follow_the_program_pipeline():
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, make_batch

    arch = get_arch("internlm2-1.8b")
    seed = 2 ** 31 + 5
    batch = make_batch(arch, ShapeConfig("t", 32, 2, "train"), 3,
                       DataConfig(seed=seed, zipf_alpha=1.1))
    toks = traffic.train_tokens(arch.vocab_size, 2, 32, seed, 3, 1.1)
    np.testing.assert_array_equal(batch["tokens"], toks[:, :-1])
    np.testing.assert_array_equal(batch["labels"], toks[:, 1:])
