"""The benchmark's own arithmetic: flops and bytes against hand counts, and
the seeded arrival schedule."""
import json
import sys

import numpy as np
import pytest

from bench_helpers import REPO

sys.path.insert(0, str(REPO / "bench"))
import flops  # noqa: E402
import reference  # noqa: E402
import serve  # noqa: E402


def dims(name):
    return reference.dims(json.loads(
        (REPO / "bench" / "configs" / f"{name}.json").read_text()))


def test_qwen3_4b_counts():
    m = dims("qwen3-4b")
    # q 2560x32x128, k and v 2560x8x128, o 32x128x2560, MLP 3 x 2560x9728
    assert flops.matmul_params(m) == (10_485_760 + 2 * 2_621_440
                                      + 10_485_760 + 74_711_040)
    # + two RMSNorm weights of 2560 and the q/k norms of 128
    assert flops.layer_params(m) == 100_925_440 + 5_120 + 256
    # 36 layers, the final norm, embedding and untied head
    assert flops.n_params(m) == 36 * 100_930_816 + 2_560 + 2 * 388_956_160
    assert flops.n_params(m) == 4_411_424_256
    assert flops.kv_bytes(m) == 36 * 2 * 8 * 128 * 2 == 147_456
    assert flops.weight_bytes(m) == 2 * (36 * 100_930_816 + 2_560
                                         + 388_956_160)


def test_step_counts_by_hand():
    m = dims("qwen3-4b")
    tok = 2 * 36 * 100_925_440
    head = 2 * 2560 * 151_936
    per_key = 4 * 36 * 32 * 128
    # two live rows at positions 10 and 20 in one round
    f, b = flops.decode(m, 1, [10, 20])
    assert f == 2 * (tok + head) + per_key * (11 + 21)
    assert b == flops.weight_bytes(m) + 147_456 * (32 + 2) + 2 * 2560 * 2
    f, b = flops.prefill(m, 4)
    assert f == 4 * tok + head + per_key * (1 + 2 + 3 + 4)
    assert b == flops.weight_bytes(m) + 147_456 * 4 + 4 * 2560 * 2


def test_arrivals_repeat_for_a_seed():
    a = serve.arrival_offsets(4.0, 30.0, 2_147_483_659)
    b = serve.arrival_offsets(4.0, 30.0, 2_147_483_659)
    c = serve.arrival_offsets(4.0, 30.0, 7)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 120
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 30.0
    # every seed offers the same gaps (they fill the window), in another
    # order
    assert not np.array_equal(a, c)
    gaps = lambda x: np.sort(np.diff(np.append(x, 30.0)))
    np.testing.assert_allclose(gaps(a), gaps(c))
    assert np.mean(gaps(a)) == pytest.approx(0.25)


def test_prompts_repeat_for_a_seed():
    a = serve.prompts_for(3_000_000_123, 5, 16, 512)
    np.testing.assert_array_equal(a, serve.prompts_for(3_000_000_123, 5, 16,
                                                       512))
    assert a.shape == (5, 16) and a.dtype == np.int32
    assert 0 <= a.min() and a.max() < 512
