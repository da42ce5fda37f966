import traffic


def _mix(kind="poisson", block=20):
    arrival = ({"kind": "poisson", "rate_per_s": 0.3} if kind == "poisson"
               else {"kind": "backlog", "jobs": 60})
    return {"arrival": arrival,
            "prompt_tokens": {"values": [128, 256, 512, 1024],
                              "weights": [0.4, 0.3, 0.2, 0.1]},
            "output_tokens": {"values": [32, 64, 128, 256],
                              "weights": [0.35, 0.3, 0.2, 0.15]},
            "block": block, "order_seed": 1}


def test_same_seed_same_inputs():
    seed = 3_000_000_019  # past 32 signed bits: seeds may be that large
    for kind in ("poisson", "backlog"):
        assert traffic.schedule(_mix(kind), 51) == traffic.schedule(_mix(kind), 51)
    a = traffic.prompt_tokens(seed, 3, 128, 49155)
    assert a.tolist() == traffic.prompt_tokens(seed, 3, 128, 49155).tolist()
    assert a.tolist() != traffic.prompt_tokens(seed + 1, 3, 128, 49155).tolist()
    assert a.min() >= 0 and a.max() < 49155


def test_the_mix_orders_the_work():
    """The order is the mix's own: another ``order_seed`` reorders the same
    gaps and lengths, and the run's seed does not enter the schedule."""
    a = traffic.schedule(_mix(block=15), 51)
    other = dict(_mix(block=15), order_seed=2)
    b = traffic.schedule(other, 51)
    assert a != b
    assert len(a) == len(b) == round(0.3 * 51)
    gaps = lambda s: sorted(round(y.due_s - x.due_s, 9) for x, y in zip(s, s[1:]))
    # the same gaps, less the one that falls after the last arrival
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2
    assert a[0].due_s == 0 and a[-1].due_s < 51
    # one block of 15 is the whole window: every length in proportion
    assert sorted(r.prompt_len for r in a) == sorted(
        [128] * 6 + [256] * 5 + [512] * 3 + [1024])
    assert sorted(r.output_len for r in a) == sorted(
        [32] * 5 + [64] * 5 + [128] * 3 + [256] * 2)


def test_backlog_blocks():
    c = traffic.schedule(_mix("backlog"), 51)
    assert len(c) == 60 and all(r.due_s == 0 for r in c)
    for block in range(0, 60, 20):
        assert sorted(r.prompt_len for r in c[block:block + 20]) == sorted(
            [128] * 8 + [256] * 6 + [512] * 4 + [1024] * 2)


def test_block_counts_largest_remainder():
    assert traffic.block_counts([0.35, 0.3, 0.2, 0.15], 20) == [7, 6, 4, 3]
    assert traffic.block_counts([0.4, 0.35, 0.25], 20) == [8, 7, 5]
    assert traffic.block_counts([0.4, 0.3, 0.2, 0.1], 15) == [6, 5, 3, 1]
    assert sum(traffic.block_counts([1, 1, 1], 20)) == 20
