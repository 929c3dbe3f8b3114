"""The benchmark's arithmetic: window, rate, percentile, FLOPs, peaks."""
import itertools

import pytest

from perfbench import window
from perfbench.reference import dense


def test_cycles_are_seeded_permutations():
    names = ["a", "b", "c", "d", "e"]
    one = list(itertools.islice(window.cycle_orders(names, 2**31 + 3), 4))
    two = list(itertools.islice(window.cycle_orders(names, 2**31 + 3), 4))
    assert one == two
    assert all(sorted(c) == names for c in one)
    other = list(itertools.islice(window.cycle_orders(names, 7), 4))
    assert other != one


def test_window_runs_whole_cycles_past_its_seconds():
    t = [0.0]

    def clock():
        return t[0]

    def run_query(name):
        t[0] += 1.0
        return name

    orders = iter([["a", "b", "c"]] * 10)
    records, t0, t1 = window.run_window(orders, run_query, 4.0, clock)
    # 4 s pass inside the second cycle, which still finishes
    assert records == ["a", "b", "c"] * 2
    assert (t0, t1) == (0.0, 6.0)
    assert window.rate(len(records), t0, t1) == pytest.approx(1.0)


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 95, 5.0),
    (list(range(1, 21)), 95, 19),
    (list(range(1, 101)), 95, 95),
    ([3, 1, 2], 50, 2),
    (list(range(1, 11)), 100, 10),
])
def test_percentile_nearest_rank(values, q, want):
    assert window.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        window.percentile([], 95)


STABLELM = {"num_hidden_layers": 32, "hidden_size": 2560,
            "num_attention_heads": 32, "num_key_value_heads": 32,
            "intermediate_size": 6912, "vocab_size": 50304}


def test_stablelm_matmul_params_match_the_program_count():
    from repro.configs import stablelm_3b
    cfg = stablelm_3b.full()
    # the program counts the embedding, the head and two norm gains a
    # layer; only the embedding gather and the gains take no matmul
    embed = cfg.vocab_size * cfg.d_model
    gains = 2 * cfg.d_model * cfg.num_layers
    assert dense.matmul_params(STABLELM) == \
        cfg.param_count() - embed - gains
    assert dense.matmul_params(STABLELM) == 2_666_332_160


def test_request_flops_by_hand():
    m = {"num_hidden_layers": 2, "hidden_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "intermediate_size": 8, "vocab_size": 10}
    per_layer = 4 * 4 * 4 + 3 * 4 * 8
    params = 2 * per_layer + 4 * 10
    assert dense.matmul_params(m) == params
    attn = 4 * 2 * 4
    # 3 prompt tokens attend to 1, 2, 3 keys, and prefill gives the first
    # of 2 answer tokens; one decode pass, at position 3, to 4 keys
    want = (3 * 2 * params + attn * 6) + (2 * params + attn * 4)
    assert dense.request_flops(m, 3, 2) == want
    # an answer of one token is all prefill
    assert dense.request_flops(m, 3, 1) == dense.request_flops(m, 3, 0) \
        == 3 * 2 * params + attn * 6


def test_peaks_refuse_an_unknown_device():
    from perfbench.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_checked_sample_is_distinct_nested_and_holds_the_longest():
    from perfbench.harness import _sample_served
    # 14 distinct (prompt, tokens), each served several times
    served = [(f"p{i % 7}" + " w" * (i % 7), [2 + i % 2])
              for i in range(42)]
    big = _sample_served(served, 2**33 + 1, 9)
    assert len(big) == 9 and len({(p, tuple(t)) for p, t in big}) == 9
    assert big[0][0] == max((p for p, _ in served), key=lambda p: len(
        p.split()))
    assert _sample_served(served, 2**33 + 1, 4) == big[:4]
    assert len(_sample_served(served, 5, 100)) == 14


def test_verdicts_are_read_from_the_first_served_token():
    from perfbench.harness import served_verdicts
    from perfbench.reference.lm import NO, YES
    v = served_verdicts([("a", [YES]), ("b", [NO]), ("c", [100, YES]),
                         ("d", [])])
    assert v == {"a": True, "b": False, "c": False, "d": False}
