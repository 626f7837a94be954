"""The yardstick's arithmetic: percentiles, intervals, FLOPs and bytes,
and the traffic generator."""
import numpy as np
import pytest

from bench import flops, stats
from bench.traffic.generate import generate

QWEN = {"hidden_size": 2048, "intermediate_size": 11008,
        "num_attention_heads": 16, "num_key_value_heads": 2,
        "num_hidden_layers": 36, "vocab_size": 151936, "model_type": "qwen2"}


@pytest.mark.parametrize("p", [0, 10, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
def test_percentile_matches_linear_interpolation(p, n):
    x = np.random.default_rng(n).lognormal(size=n)
    assert stats.percentile(x, p) == pytest.approx(np.percentile(x, p),
                                                   rel=1e-12)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_intervals_union_gaps_and_clip():
    iv = stats.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert iv == [(0, 3), (5, 8)]
    assert stats.covered(iv) == 6
    assert stats.gaps(iv, -1, 12) == [(-1, 0), (3, 5), (8, 12)]
    assert stats.clip_intervals(iv, 2, 6) == [(2, 3), (5, 6)]


def test_matmul_params_of_qwen2_5_3b():
    per_layer = 2048 * (16 + 4) * 128 + 16 * 128 * 2048 + 3 * 2048 * 11008
    assert flops.matmul_params(QWEN) == 36 * per_layer
    # 2.77e9 matmul weights + 0.31e9 embedding = the published 3.09B
    assert flops.matmul_params(QWEN) + 151936 * 2048 == pytest.approx(
        3.09e9, rel=0.01)


@pytest.mark.parametrize("p,want", [(0, 1), (15, 16), (16, 17), (1023, 1024),
                                    (1024, 1025), (1039, 1040), (1040, 1025),
                                    (5000, 1024 + 5000 % 16 + 1)])
def test_visible_keys_under_budget(p, want):
    assert flops.visible_keys(p, 1024, 16) == want


@pytest.mark.parametrize("start,n", [(0, 128), (128, 128), (1024, 128),
                                     (2048, 37)])
def test_chunk_visible_keys_is_the_sum_over_its_queries(start, n):
    kept = 16 * min(start // 16, 64)
    assert flops.chunk_visible_keys(start, n, 1024, 16) == sum(
        kept + i + 1 for i in range(n))


def test_step_flops_counts_tokens_rows_and_keys():
    d = flops.step_flops(QWEN, [("d", 2000)], 1024, 16)
    assert d == (2 * flops.matmul_params(QWEN) + flops.lm_head_flops(QWEN)
                 + 36 * 4 * 16 * 128 * flops.visible_keys(2000, 1024, 16))
    p = flops.step_flops(QWEN, [("p", 0, 128)], 1024, 16)
    assert p == (2 * flops.matmul_params(QWEN) * 128
                 + flops.lm_head_flops(QWEN) + 36 * 4 * 16 * 128 * 128 * 129 // 2)
    assert flops.step_flops(QWEN, [("d", 2000), ("p", 0, 128)], 1024, 16) \
        == d + p


def test_kernel_bytes_read_each_key_once():
    f, b = flops.decode_row_work(QWEN, 3000, 1024, 16)
    keys = flops.visible_keys(3000, 1024, 16)
    assert b == 36 * (2 * keys * 2 * 128 * 2 + 2 * 16 * 128 * 2)
    assert f == 36 * 4 * 16 * 128 * keys


MIX_OPEN = {"loop": "open", "rate_per_s": 3.0,
            "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.6,
                           "min": 64, "max": 512},
            "output_len": {"dist": "uniform", "min": 32, "max": 448},
            "shape_seed": 5}
MIX_CLOSED = {"loop": "closed", "requests": 16, "max_new_tokens": 99,
              "prompt_len": {"dist": "uniform", "min": 10, "max": 20},
              "shape_seed": 6}


@pytest.mark.parametrize("mix", [MIX_OPEN, MIX_CLOSED])
def test_generator_repeats_exactly_for_a_seed(mix):
    a = generate(mix, 2**33 + 17, -5.0, 20.0, 1000)
    b = generate(mix, 2**33 + 17, -5.0, 20.0, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due) == (y.max_new_tokens, y.due)


@pytest.mark.parametrize("mix", [MIX_OPEN, MIX_CLOSED])
def test_seeds_share_sizes_and_arrivals_in_another_order(mix):
    a = generate(mix, 1, -5.0, 20.0, 1000)
    b = generate(mix, 2, -5.0, 20.0, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [r.due for r in a] == [r.due for r in b]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert any(not np.array_equal(x.prompt[:10], y.prompt[:10])
               for x, y in zip(a, b))
    lens = [len(r.prompt) for r in a]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert min(lens) >= lo and max(lens) <= hi


def test_open_loop_arrivals_are_in_the_window_at_the_rate():
    a = generate(MIX_OPEN, 3, -10.0, 200.0, 1000)
    due = [r.due for r in a]
    assert due == sorted(due) and -10.0 <= due[0] and due[-1] < 200.0
    assert len(a) == pytest.approx(630, rel=0.15)
