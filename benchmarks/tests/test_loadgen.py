"""The load generator's clock: latency runs from the instant a request
was DUE, and how late the generator itself ran is reported."""
import threading
import time

from benchmarks.drivers import serve


class Handle:
    def __init__(self, prompt, asked):
        self.prompt, self.asked = prompt, asked
        self.t_first_token = self.t_done = None
        self.n_generated = 0
        self.done = threading.Event()

    def wait(self, timeout):
        assert self.done.wait(timeout)
        return self.prompt + [7] * self.asked


class SlowServer:
    """Admission blocks for ``admit_s`` (a starved generator); the
    first token comes ``first_s`` after admission."""

    def __init__(self, admit_s, first_s):
        self.admit_s, self.first_s = admit_s, first_s

    def generate_async(self, prompt, asked, temperature):
        time.sleep(self.admit_s)
        h = Handle(prompt, asked)

        def finish():
            time.sleep(self.first_s)
            h.t_first_token = time.monotonic()
            h.n_generated = asked
            h.t_done = h.t_first_token + 0.001 * (asked - 1)
            h.done.set()

        threading.Thread(target=finish, daemon=True).start()
        return h

    def stats(self):
        return {"queue_depth": 0, "tokens_generated": 0}


class Ctx:
    trace = False
    traffic = {"trace_seconds": 0, "warm_seconds": 0.0,
               "warm_completions": 0}

    class watch:
        @staticmethod
        def snapshot():
            return {"lowered": 0}


TRAFFIC = {"rate_rps": 20, "shape_seed": 3, "max_total": 64,
           "warm_seconds": 2.0, "warm_burst": 5, "warm_completions": 3,
           "prompt_len": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
           "new_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


def test_latency_runs_from_due_time_and_lateness_is_reported():
    # ten requests all due in the first 10 ms; admission takes 30 ms
    # each, so the generator falls ~270 ms behind by the last one
    schedule = [(0.001 * i, [1, 2, 3], 4) for i in range(10)]
    sent, t0, ends = serve.serve_window(Ctx(), SlowServer(0.03, 0.02),
                                        schedule, 0.5, 5.0)
    assert set(ends) == {"t0", "t_end"}
    s = serve.summarize(sent, t0, 0.5, vocab=10)
    assert s["attempted"] == 10 and s["failed"] == 0
    # from the SEND instant every first token would look ~50 ms away;
    # from the due instant the last ones waited ~300 ms
    assert s["ttft_p95_ms"] > 250
    assert s["loadgen.late_p95_ms"] > 200
    assert 0.5 < s["gap_p95_ms"] < 2
    # all ten were seen to complete inside the window: 4 tokens each
    assert s["completed_in_window"] == 10
    assert s["serve_tokens_per_s"] == 40 / 0.5


def test_every_seed_replays_the_same_trace_with_other_tokens():
    a = serve.make_schedule(TRAFFIC, 1, 5.0, 100)
    b = serve.make_schedule(TRAFFIC, 2**31 + 12345, 5.0, 100)
    assert [(d, len(p), n) for d, p, n in a] == [(d, len(p), n)
                                                 for d, p, n in b]
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    assert a == serve.make_schedule(TRAFFIC, 1, 5.0, 100)
    assert all(len(p) + n <= 64 and n >= 1 for _, p, n in a)


def test_the_trace_opens_with_a_burst_and_a_longer_window_only_adds():
    s = serve.make_schedule(TRAFFIC, 1, 5.0, 100)
    due = [d for d, _, _ in s]
    assert due == sorted(due) and due[:5] == [0.0] * 5 and due[5] > 0
    assert due[-1] < 7.0
    assert 100 < len(s) - 5 < 180           # 20 a second over 7 s
    longer = serve.make_schedule(TRAFFIC, 1, 9.0, 100)
    assert longer[:len(s)] == s and len(longer) > len(s)


def test_the_window_opens_at_the_nth_completion():
    class C(Ctx):
        traffic = dict(Ctx.traffic, warm_seconds=5.0, warm_completions=3)
    # one request every 300 ms, each done 150 ms after it was sent: the
    # third completes 750 ms in, and the window runs 675 ms from there
    # (one token each: the fake stamps t_done at the instant it is seen)
    schedule = [(0.3 * i, [1, 2, 3], 1) for i in range(20)]
    sent, t0, _ = serve.serve_window(C(), SlowServer(0.0, 0.15), schedule,
                                     0.675, 5.0)
    assert 0.75 <= t0 - sent[0]["due"] < 0.82
    s = serve.summarize(sent, t0, 0.675, vocab=10)
    # done at 1,050 and 1,350 ms; due at 900 and 1,200 ms; end at 1,425
    assert s["completed_in_window"] == 2 and s["due_in_window"] == 2
    assert len(sent) == 5                   # nothing is sent after the end
    # and at ``warm_seconds`` at the latest
    C.traffic = dict(C.traffic, warm_seconds=0.4, warm_completions=99)
    sent, t0, _ = serve.serve_window(C(), SlowServer(0.0, 0.15), schedule,
                                     0.675, 5.0)
    assert 0.4 <= t0 - sent[0]["due"] < 0.47


def rec(due, first, done, n):
    class H:
        t_first_token, t_done, n_generated = first, done, n
    return {"due": due, "sent": due, "prompt": [1], "asked": n,
            "handle": H(), "error": None, "tokens": [1] + [2] * n}


def test_only_requests_seen_to_complete_in_the_window_count():
    sent = [rec(8.0, 9.0, 10.5, 5),     # warm stretch, done in the window
            rec(9.5, 9.8, 9.9, 7),      # done before the window opened
            rec(10.2, 10.5, 11.0, 3),   # due and done in the window
            rec(10.4, 11.0, 12.5, 9)]   # still running at its end
    s = serve.summarize(sent, t0=10.0, seconds=1.5, vocab=10)
    assert s["serve_tokens_per_s"] == (5 + 3) / 1.5
    assert s["completed_in_window"] == 2 and s["failed"] == 0
    assert s["attempted"] == 4 and s["due_in_window"] == 2
    # tails over the two requests due in the window, from the due instant
    assert abs(s["ttft_p50_ms"] - 1e3 * (0.3 + 0.6) / 2) < 1e-6
    # 10.5 is 0.5 after the opening, 11.0 is 0.5 before the end
    assert abs(s["completion_nearest_an_end_s"] - 0.5) < 1e-9


def test_wrong_length_counts_as_failed():
    schedule = [(0.0, [1, 2, 3], 4)]
    sent, t0, _ = serve.serve_window(Ctx(), SlowServer(0.0, 0.01),
                                     schedule, 0.1, 5.0)
    sent[0]["tokens"] = sent[0]["tokens"][:-1]
    s = serve.summarize(sent, t0, 0.1, vocab=10)
    assert s["failed"] == 1 and s["wrong"] == 1
