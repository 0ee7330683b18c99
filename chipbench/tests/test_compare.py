"""The comparison's limits: a largest value for each gap and count, and a
least number of rounds compared over the pool's streams together."""
from chipbench import compare


def test_least_compared_rounds():
    limits = {"loss_gap": 1e-6, "min_compared_rounds": 1000}
    streams = [{"loss_gap": 1e-7, "eps_gap": 0.0, "sync_mismatch": 0,
                "bytes_mismatch": 0, "error_mismatch": 0, "compared_rounds": n}
               for n in (700, 250)]
    numbers = compare.combine(streams)
    assert numbers["compared_rounds"] == 950
    assert not compare.within(numbers, limits)
    assert compare.value_of("min_compared_rounds", numbers) == 950
    numbers["compared_rounds"] = 1000
    assert compare.within(numbers, limits)
    numbers["loss_gap"] = 2e-6
    assert not compare.within(numbers, limits)
