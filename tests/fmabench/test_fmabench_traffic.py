"""The traffic generators: the same seed gives the same trace, and the seed
changes the order of the work, never the work."""

import statistics

import pytest

from fmabench import spec, traffic

BENCH = spec.benchmark()
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


def mix(name):
    return spec.load_json(spec._data_file("traffic", name))


def take(it, n):
    return [next(it) for _ in range(n)]


def stream(m, seed, tag):
    """A mix's requests; an open mix without a pool of its own gets the 140
    arrivals of a 50 s window, as ``open_schedule`` gives it."""
    return traffic.requests_stream(m, 32000, seed, tag, pool=140)


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_same_seed_same_requests(name):
    a = take(stream(mix(name), 2**31 + 11, "window"), 40)
    b = take(stream(mix(name), 2**31 + 11, "window"), 40)
    c = take(stream(mix(name), 5, "window"), 40)
    assert traffic.digest(a) == traffic.digest(b) != traffic.digest(c)
    w = take(stream(mix(name), 5, "warmup"), 40)
    assert traffic.digest(w) != traffic.digest(c)


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_every_seed_offers_the_same_work_in_another_order(name):
    m = mix(name)
    n = m.get("pool", 140)

    def shapes(seed):
        reqs = take(stream(m, seed, "window"), n)
        return [(len(r["prompt"]), r["max_tokens"]) for r in reqs]

    a, b = shapes(1), shapes(987654321987)
    assert a != b and sorted(a) == sorted(b)
    lo, hi = m["prompt_len"]["min"], m["prompt_len"]["max"]
    assert all(lo <= p <= hi for p, _ in a)
    assert all(
        m["output_len"]["min"] <= o <= m["output_len"]["max"] for _, o in a
    )
    med = statistics.median(p for p, _ in a)
    assert abs(med - m["prompt_len"]["median"]) <= 0.03 * m["prompt_len"]["median"]


def test_token_ids_are_seeded_and_in_the_vocabulary():
    a = traffic.Prompts(32000, 3, "x").draw(500)
    assert a == traffic.Prompts(32000, 3, "x").draw(500)
    assert a != traffic.Prompts(32000, 4, "x").draw(500)
    assert min(a) >= 1 and max(a) < 32000


def test_open_schedule_is_one_circle_cut_where_the_seed_says():
    m = mix("chat")
    _, a = traffic.open_schedule(m, 32000, 1, 50.0)
    _, b = traffic.open_schedule(m, 32000, 1, 50.0)
    wc, c = traffic.open_schedule(m, 32000, 2**31 + 2, 50.0, warm_s=6.0)
    assert traffic.digest(a) == traffic.digest(b) != traffic.digest(c)
    want = round(m["rate_rps"] * 50.0)
    assert len(a) == len(c) == want
    times = [r["t_s"] for r in a]
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 50.0
    # no pool in the file: the window holds every shape of its pool once
    shape = lambda r: (len(r["prompt"]), r["max_tokens"])  # noqa: E731
    assert "pool" not in m
    assert sorted(map(shape, a)) == sorted(map(shape, c))
    # ... in the same circular order, begun at another point
    sa, sc = [shape(r) for r in a], [shape(r) for r in c]
    k = next(i for i in range(want) if sc[i:] + sc[:i] == sa)
    assert k > 0
    ga = [round(y - x, 4) for x, y in zip(times, times[1:])]
    tc = [r["t_s"] for r in c]
    gc = [round(y - x, 4) for x, y in zip(tc, tc[1:])]
    assert sorted(traffic.stratified_gaps(m["rate_rps"], want))[0] > 0
    both = set(ga) & set(gc)
    assert len(both) >= want - 8  # the same gaps (rounded) but for the one at each cut
    # the warm-up stretch is the arc that ends at the cut
    assert wc and 0.0 <= wc[0]["t_s"] and wc[-1]["t_s"] < 6.0
    assert [shape(r) for r in wc] == (sc[-len(wc):] if len(wc) <= want else None)
    assert traffic.open_schedule(m, 32000, 5, 50.0)[0] == []


def test_stratified_gaps_have_the_poisson_mean_and_spread():
    gaps = traffic.stratified_gaps(4.0, 200)
    assert sum(gaps) == pytest.approx(200 / 4.0)
    # an exponential's standard deviation equals its mean
    assert statistics.pstdev(gaps) == pytest.approx(0.25, rel=0.08)


@pytest.mark.parametrize("name", MIXES)
def test_mix_fits_its_engine(name):
    """No operation can fail: the longest request fits the context, and the
    pool holds the worst case, so nothing is preempted."""
    m = mix(name)
    opts = m["engine_options"].split()
    get = lambda flag: int(opts[opts.index(flag) + 1])  # noqa: E731
    longest = m["prompt_len"]["max"] + m["output_len"]["max"]
    assert longest <= 4096
    assert get("--max-batch") * longest <= get("--num-pages") * get("--page-size")
    if m["kind"] == "closed":
        assert m["clients"] <= get("--max-batch")


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert traffic.percentile(xs, 95) == 95
    assert traffic.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)
