from benchmarks.chip import traffic

SHARED = traffic.load_mix("code-agent")
# an offline batch: a shape of mix the generator reads that no cell uses yet
OFFLINE = dict(traffic.load_mix("chat"), name="offline", loop="offline",
               requests_per_s=2.0)
MIXES = [traffic.load_mix("chat"), SHARED, OFFLINE]


def _sig(items):
    return [(it.prompt, it.new_tokens, round(it.arrival_s, 9), it.prefix)
            for it in items]


def test_each_mix_is_deterministic_for_a_seed_and_differs_on_another():
    for mix in MIXES:
        a = traffic.generate(mix, 2**33 + 11, 20, (1, 5000))
        b = traffic.generate(mix, 2**33 + 11, 20, (1, 5000))
        c = traffic.generate(mix, 2**33 + 12, 20, (1, 5000))
        assert _sig(a) == _sig(b)
        assert _sig(a) != _sig(c)
        # the same amount of work on every seed, in another order
        assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in c)
        assert sorted(i.new_tokens for i in a) == sorted(i.new_tokens for i in c)


def test_a_schedule_seed_replays_one_schedule_and_the_seed_draws_the_ids():
    for base in (traffic.load_mix("chat"), SHARED):
        mix = dict(base, schedule_seed=5)
        a = traffic.generate(mix, 2**33 + 11, 20, (1, 5000))
        c = traffic.generate(mix, 2**33 + 12, 20, (1, 5000))
        shape = [[(len(i.prompt), i.new_tokens, i.arrival_s, i.prefix)
                  for i in items] for items in (a, c)]
        assert shape[0] == shape[1]
        assert [i.prompt for i in a] != [i.prompt for i in c]
        free = traffic.generate(base, 2**33 + 11, 20, (1, 5000))
        assert sorted(len(i.prompt) for i in free) == sorted(
            len(i.prompt) for i in a)


def test_lengths_and_arrivals_follow_the_mix():
    mix = traffic.load_mix("chat")
    items = traffic.generate(mix, 7, 40, (1, 5000))
    assert len(items) == round(40 * mix["rate_per_s"])
    lens = sorted(len(i.prompt) for i in items)
    assert mix["prompt_len"]["min"] <= lens[0] and lens[-1] <= mix["prompt_len"]["max"]
    t = [i.arrival_s for i in items]
    assert t == sorted(t) and t[0] == 0.0 and 0.8 * 40 < t[-1] < 1.2 * 40
    batch = traffic.generate(OFFLINE, 7, 10, (1, 50))
    assert all(i.arrival_s == 0.0 for i in batch)


def test_shared_prefixes_are_zipf_dealt_and_ids_stay_in_range():
    mix = SHARED
    items = traffic.generate(mix, 3, 200, (100, 200))
    counts = traffic.prefix_counts(mix["shared_prefix"], len(items))
    assert counts[0] == max(counts) and counts.sum() == len(items)
    by = {}
    for it in items:
        by.setdefault(it.prefix, set()).add(it.prompt[: mix["shared_prefix"]["len"]])
        assert all(100 <= t < 200 for t in it.prompt)
    assert all(len(v) == 1 for v in by.values())
    rehearse = traffic.rehearsal(mix, 4, (300, 400), n=4, cuts=[16, 32], new_tokens=2)
    assert [len(it.prompt) for it in rehearse] == [16, 32, 16, 32]
    assert all(min(it.prompt) >= 300 for it in rehearse)
