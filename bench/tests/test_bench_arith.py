"""The harness's arithmetic on synthetic inputs: the open- and closed-loop
schedules, lateness, the end-to-end metrics and the per-layer readers."""
import numpy as np
import pytest

from bench.harness import e2e, seeds, spec
from bench.harness.cell import Context
from bench.harness.driver import Record, Step

BENCH = spec.Benchmark()
GEN = BENCH.generator("requests")
CHAT = BENCH.traffic("chat")["params"]


def schedule(seed, rate=8.0, horizon=30.0):
    return GEN.generate(CHAT, rng=seeds.rng(seed, seeds.TRAFFIC),
                        arrivals={"kind": "poisson", "rate_per_s": rate},
                        horizon_s=horizon, vocab=49152, max_len=2048)


def closed(seed, clients=32, rounds=3):
    return GEN.generate(CHAT, rng=seeds.rng(seed, seeds.TRAFFIC),
                        arrivals={"kind": "closed", "clients": clients,
                                  "rounds": rounds},
                        horizon_s=0.0, vocab=49152, max_len=2048)


def test_schedule_is_a_function_of_the_seed():
    a, b = schedule(2 ** 40 + 3), schedule(2 ** 40 + 3)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = schedule(5)
    assert [r.due for r in a] != [r.due for r in c]


def test_every_seed_offers_the_same_work_per_block():
    block = CHAT["block"]
    a, c = schedule(11, horizon=1e4)[:block], schedule(12, horizon=1e4)[:block]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert sum(r.shared >= 0 for r in a) == block // 2
    # each request is due in the middle of its gap, and a block spans
    # exactly block / rate seconds
    ga, gc = gaps_of(a), gaps_of(c)
    assert np.allclose(np.sort(ga), np.sort(gc))
    assert ga.sum() == pytest.approx(block / 8.0)
    assert 0.0 < a[0].due and a[-1].due < block / 8.0


def gaps_of(reqs):
    g, end = [], 0.0
    for r in reqs:
        g.append(2 * (r.due - end))
        end += g[-1]
    return np.array(g)


@pytest.mark.parametrize("origin", [0.0, 7.5, 20.0])
def test_a_window_at_the_origin_holds_one_block_for_every_seed(origin):
    block, rate = CHAT["block"], 8.0
    span = block / rate
    lens = []
    for seed in (3, 2 ** 40 + 1):
        reqs = GEN.generate(CHAT, rng=seeds.rng(seed, seeds.TRAFFIC),
                            arrivals={"kind": "poisson", "rate_per_s": rate},
                            horizon_s=origin + 3 * span, vocab=49152,
                            max_len=2048, origin_s=origin)
        assert all(r.due >= 0 for r in reqs)
        inside = [r for r in reqs if origin <= r.due < origin + span]
        assert len(inside) == block
        # none on the window's edges
        assert min(r.due for r in inside) > origin + 1e-4
        assert max(r.due for r in inside) < origin + span - 1e-4
        lens.append(sorted(len(r.prompt) for r in inside))
    assert lens[0] == lens[1]


def test_schedule_respects_lengths_and_shared_prompts():
    reqs = schedule(3, horizon=200.0)
    sys_prompts = {}
    for r in reqs:
        assert 32 <= len(r.prompt) <= 1536
        assert 1 <= r.max_new <= 384 and len(r.prompt) + r.max_new <= 2048
        if r.shared >= 0:
            head = r.prompt[:256].tobytes()
            assert sys_prompts.setdefault(r.shared, head) == head
    assert len(sys_prompts) == 4


def test_closed_loop_schedule_spreads_the_first_round():
    block = CHAT["block"]
    a, c = closed(2 ** 41 + 7), closed(13)
    assert len(a) == 3 * 32 and [r.client for r in a[:33]] == [
        *range(32), 0]
    assert all(r.due == 0.0 for r in a)
    # the same work for every seed, the first block's answers cut to
    # evenly spread shares of the full answers
    for lo, hi in ((0, block), (block, 2 * block)):
        assert (sorted(r.max_new for r in a[lo:hi])
                == sorted(r.max_new for r in c[lo:hi]))
        assert (sorted(len(r.prompt) for r in a[lo:hi])
                == sorted(len(r.prompt) for r in c[lo:hi]))
    full = GEN.lognormal_lengths(CHAT["answer"], block)
    cut = sorted(r.max_new for r in a[:block])
    assert cut[0] < full[0] and cut[-1] < full[-1]
    assert sum(cut) < 0.7 * sum(full)
    assert sorted(r.max_new for r in a[block:2 * block]) == list(full)


class FakeEngine:
    """Just enough of ServeEngine for the client loop: each step admits every
    queued request and gives each live one token."""
    prefill_chunk = 4
    n_slots = 2

    def __init__(self, clock):
        self.clock = clock
        self.queue, self.finished = [], {}
        self.slot_req = [None, None]
        self.slot_out = [[], []]
        self.slot_pos = np.zeros(2, np.int64)
        self.active = np.zeros(2, bool)
        self.occ = {"prefill_tokens": 0, "decode_tokens": 0,
                    "slot_steps": 0, "slot_active_steps": 0}
        self.prefix_events = {"prefix_tokens_skipped": 0}
        self.step_count = 0

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        self.clock.t += 0.01
        for s in range(2):
            if self.slot_req[s] is None and self.queue:
                self.slot_req[s] = self.queue.pop(0)
                self.slot_out[s] = []
                self.active[s] = True
        for s in range(2):
            r = self.slot_req[s]
            if r is None:
                continue
            self.slot_out[s].append(1)
            self.slot_pos[s] += 1
            self.occ["decode_tokens"] += 1
            if len(self.slot_out[s]) >= r.max_new_tokens:
                self.finished[r.rid] = type("F", (), dict(
                    rid=r.rid, tokens=np.ones(r.max_new_tokens),
                    finish_reason="length"))
                self.slot_req[s] = None
                self.active[s] = False
        self.step_count += 1


class Clock:
    t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def test_open_loop_times_requests_from_their_due_time():
    from bench.harness.driver import Loop
    req = GEN.Req
    sched = [req(0, 0.0, np.zeros(3, np.int32), 2),
             req(1, 0.005, np.zeros(3, np.int32), 2),
             req(2, 0.5, np.zeros(3, np.int32), 3)]
    clock = Clock()
    eng = FakeEngine(clock)
    drv = Loop(eng, sched, 100.0, clock=clock, sleep=clock.sleep)
    drv.run_until(101.0)
    recs = drv.records
    assert [recs[i].due for i in range(3)] == [100.0, 100.005, 100.5]
    # request 1 came due while request 0's step ran: it is submitted late,
    # and its wait counts from its due time, not from its submission
    late = drv.lateness(100.0, 101.0)
    assert late.min() >= 0 and late.max() == pytest.approx(0.005)
    assert recs[1].submit == pytest.approx(100.01)
    assert recs[1].vis[0][0] - recs[1].due == pytest.approx(0.015)
    assert recs[2].submit == pytest.approx(100.5)       # slept until due
    assert [r.reason for r in recs.values()] == ["length"] * 3
    assert [r.n for r in recs.values()] == [2, 2, 3]
    assert sum(s.tokens for s in drv.steps) == 7


def test_closed_loop_sends_a_clients_next_request_when_its_last_ends():
    from bench.harness.driver import Loop
    req = GEN.Req
    sched = [req(0, 0.0, np.zeros(3, np.int32), 2, client=0),
             req(1, 0.0, np.zeros(3, np.int32), 5, client=1),
             req(2, 0.0, np.zeros(3, np.int32), 3, client=0),
             req(3, 0.0, np.zeros(3, np.int32), 1, client=1)]
    clock = Clock()
    eng = FakeEngine(clock)
    drv = Loop(eng, sched, 100.0, clock=clock, sleep=clock.sleep)
    drv.run_until(101.0)
    recs = drv.records
    assert recs[0].due == recs[1].due == 100.0
    # each next request is due when the step that finished its client's
    # last one ended, and is sent right then
    assert recs[2].due == pytest.approx(recs[0].finish)
    assert recs[2].due == pytest.approx(100.02)
    assert recs[3].due == pytest.approx(recs[1].finish)
    assert recs[2].submit == pytest.approx(recs[2].due)
    assert recs[2].vis[0][0] - recs[2].due == pytest.approx(0.01)
    assert [r.reason for r in recs.values()] == ["length"] * 4
    assert [r.n for r in recs.values()] == [2, 5, 3, 1]
    assert len(eng.queue) == 0 and not eng.active.any()


def test_end_to_end_counts_unfinished_requests_in_the_tails():
    t_open, t_end = 10.0, 20.0
    recs = [
        # finished in the window: ttft 1 s, 5 tokens over 2 s -> 0.5 s/token
        Record(0, 11.0, 10, 5, admit=11.5, vis=[(12.0, 1), (14.0, 5)],
               finish=14.0, reason="length"),
        # due in the window, never admitted: ttft and queue wait run to t_end
        Record(1, 18.0, 10, 5),
        # still decoding at the end: 3 tokens by then, first at 16, third at
        # 19 -> (19-16)/2
        Record(2, 15.0, 10, 9, admit=15.0, vis=[(16.0, 1), (19.0, 3),
                                                 (21.0, 4)]),
        # due before the window: tokens count, latencies do not
        Record(3, 5.0, 10, 4, admit=5.0, vis=[(9.0, 2), (10.5, 4)],
               finish=10.5, reason="length"),
    ]
    assert e2e.output_tokens(recs, t_open, t_end) == 5 + 3 + 2
    assert sorted(e2e.ttft_s(recs, t_open, t_end)) == [1.0, 1.0, 2.0]
    assert sorted(e2e.tpot_s(recs, t_open, t_end)) == [0.5, 1.5]
    assert sorted(e2e.queue_wait_s(recs, t_open, t_end)) == [0.0, 0.5, 2.0]
    out = e2e.end_to_end(recs, t_open, t_end)
    assert out["output_tok_per_s"] == pytest.approx(1.0)
    assert out["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([1.0, 1.0, 2.0], 95))
    assert e2e.attempted_failed(recs, t_open, t_end) == (3, 0)


def test_first_token_tail_waits_past_the_window():
    t_open, t_end = 10.0, 20.0
    recs = [
        # first token after the window's close: counted as it came
        Record(0, 19.0, 10, 5, admit=19.0, vis=[(24.0, 1), (25.0, 2)]),
        # none by the end of the wait: counted to the end of the wait
        Record(1, 19.5, 10, 5),
        # one token by t_end: no gap seen, no per-token time
        Record(2, 12.0, 10, 5, admit=12.0, vis=[(19.5, 1), (20.5, 2)]),
    ]
    assert sorted(e2e.ttft_s(recs, t_open, t_end, t_wait=30.0)) == [
        5.0, 7.5, 10.5]
    # without a wait, the tail is censored at the window's close
    assert sorted(e2e.ttft_s(recs, t_open, t_end)) == [0.5, 1.0, 7.5]
    assert e2e.tpot_s(recs, t_open, t_end) == []
    out = e2e.end_to_end(recs, t_open, t_end, t_wait=30.0)
    assert out["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([5.0, 7.5, 10.5], 95))
    assert out["tpot_p95_ms"] is None


def ctx_for(records, steps, c_open, c_close, cfg=None, peaks=None):
    return Context(cfg, records, steps, 10.0, 20.0, c_open, c_close, 3.0, 0,
                   peaks)


def test_per_layer_readers():
    recs = [Record(0, 11.0, 100, 5, admit=11.5, vis=[(12.0, 1)]),
            Record(1, 12.0, 300, 5, admit=13.0, vis=[(14.0, 1)])]
    steps = [Step(10.0 + i, 10.5 + i, 8, 800.0) for i in range(10)]
    steps.append(Step(25.0, 26.0, 8, 800.0))          # after the window
    c0 = {"slot_steps": 0, "slot_active_steps": 0,
          "prefix_tokens_skipped": 0}
    c1 = {"slot_steps": 40, "slot_active_steps": 30,
          "prefix_tokens_skipped": 100}

    class Cfg:
        n_layers, d_model, n_heads, n_kv_heads, hd, d_ff = 2, 64, 4, 2, 16, 128
        vocab_size = 256
    ctx = ctx_for(recs, steps, c0, c1, Cfg, {"int8_ops_per_s": 1e9})
    read = lambda name: BENCH.metric_reader(name).read(ctx)  # noqa: E731
    assert read("slot_occupancy") == pytest.approx(75.0)
    assert read("prefix_hit_share") == pytest.approx(25.0)
    assert read("engine_step_ms") == pytest.approx(500.0)
    assert read("queue_wait_p95_ms") == pytest.approx(
        1e3 * np.percentile([0.5, 1.0], 95))
    assert read("compile_s") == 3.0
    assert read("compiles_in_window") == 0.0
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    ops = (2 * 2 * per_layer * 80 + 2 * 64 * 256 * 2 + 4 * 2 * 4 * 16 * 8000)
    assert read("step_mfu") == pytest.approx(100 * ops / (10 * 1e9))
    assert read("device_idle_share") is None       # no trace: nothing read
