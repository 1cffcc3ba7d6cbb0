"""The benchmark's own task streams."""
import numpy as np

from chipbench import arrivals

TR = {"rate_per_stream": 0.1, "c_support": [1, 2, 4, 8],
      "c_probs": [0.35, 0.35, 0.2, 0.1], "quality_noise": 0.004}


def test_same_seed_same_streams_any_seed_same_work():
    a = arrivals.StreamSource(TR, 8, 2 ** 31 + 7, 3, chunk=64)
    b = arrivals.StreamSource(TR, 8, 2 ** 31 + 7, 3, chunk=64)
    c = arrivals.StreamSource(TR, 8, 11, 3, chunk=64)
    x, y, z = a.take(1, 100), b.take(1, 100), c.take(1, 100)
    for col in arrivals.COLS:
        np.testing.assert_array_equal(x[col], y[col])
    assert not np.array_equal(x["c"], z["c"])
    first = [s.tasks(0, 0, 64) for s in (a, c)]
    for col in ("c", "noise"):
        np.testing.assert_array_equal(np.sort(first[0][col]),
                                      np.sort(first[1][col]))
    np.testing.assert_allclose(first[0]["arr_time"][-1],
                               first[1]["arr_time"][-1], rtol=1e-12)


def test_gang_sizes_follow_the_probabilities_and_the_cluster():
    t = arrivals.chunk_template(TR, 4, 1000)
    counts = {int(v): int((t["c"] == v).sum()) for v in (1, 2, 4, 8)}
    assert counts == {1: 389, 2: 389, 4: 222, 8: 0}
    assert abs(t["gap"].mean() - 10.0) < 0.1
    assert abs(np.std(t["noise"]) - 0.004) < 2e-4


def test_take_pops_in_order_and_grows():
    s = arrivals.StreamSource(TR, 8, 5, 2, chunk=16)
    whole = s.tasks(0, 0, 40)
    got = np.concatenate([s.take(0, 7)["arr_time"] for _ in range(5)])
    np.testing.assert_array_equal(got, whole["arr_time"][:35])
    assert np.all(np.diff(whole["arr_time"]) > 0) and s.ptr[0] == 35
