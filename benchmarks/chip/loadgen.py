"""Traffic of the chip benchmark: the seeded registry, the intent sources and
the two loop kinds. One general generator, driven by a traffic file
(``traffic/<name>.json``); a new mix is a new file, never new code.

Everything the generator emits is a pure function of (traffic file, seed).
The seed never changes the WORK. A traffic file fixes one endless stream of
intents (``pool_seed``: item i is the i-th seeded ``intent_for`` wording with
its case number; nothing about the program's answers enters it) and, for a
paced loop, one stream of arrival gaps. Both are dealt in blocks of
``block`` items: every seed sends block 0, then block 1, ..., each block in
a seeded order of its own. So two seeds that send n requests have sent the
same requests up to the last, partial block, in another order; with greedy
decode and fixed weights a given intent costs the same tokens in every run.

Loop kinds (``loop``):
  closed  N callers; each sends its next request when the last one returns.
          Reported generator lateness: the gap from a reply to the next send.
  paced   open loop on a seeded schedule (``rate_per_s``, exponential gaps);
          a request's latency counts from the instant it was DUE, and the
          send lateness is reported.

Intent sources (``intents``):
  distinct  every request a new string (wording + case number), each wording
            drawn over its own three registry services: neither the plan
            cache nor a shared shortlist can answer.
  repeat    each draw is, with seeded probability ``repeat_share``, a re-send
            of an intent this run already had answered (a plan-cache hit;
            which one is seeded); the rest are fresh.
  session   distinct strings over ``families`` wordings only: variants of one
            task retrieve the same shortlist, so prompts share the services
            block (what the radix prefix cache is for).

The registry and intent builders are copied from ``mcpx/utils/synth.py``
(``synth_registry``, ``intent_for``) so that a later change there cannot
change the benchmark's traffic; the originals are listed in PERF.md.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable, Optional

# ------------------------------------------------------------------ registry
_DOMAINS = [
    "auth", "user", "order", "billing", "catalog", "search", "inventory",
    "shipping", "payment", "fraud", "notify", "report", "analytics", "geo",
    "translate", "summarize", "extract", "rank", "recommend", "audit",
]
_VERBS = ["fetch", "validate", "enrich", "score", "transform", "merge", "route", "sync"]
_KEYS = [
    "query", "user_id", "order_id", "document", "text", "items", "amount",
    "address", "score", "status", "report", "features", "vector", "summary",
]


def build_registry(n: int, seed: int) -> list[dict]:
    """``n`` chained service records as plain dicts (the registry file's
    rows): the in-distribution universe of ``synth_registry``, draw for
    draw."""
    rng = random.Random(seed)
    records = []
    for i in range(n):
        a = _DOMAINS[i % len(_DOMAINS)]
        b = _VERBS[(i // len(_DOMAINS)) % len(_VERBS)]
        name = f"{a}-{b}-{i:04d}"
        n_in = rng.randint(1, 3)
        n_out = rng.randint(1, 2)
        input_keys = rng.sample(_KEYS, n_in)
        output_keys = rng.sample(_KEYS, n_out)
        records.append(
            {
                "name": name,
                "endpoint": f"http://{name}",
                "description": f"{b}s {a} data for downstream composition",
                "input_schema": {k: "str" for k in input_keys},
                "output_schema": {k: "str" for k in output_keys},
                "cost_profile": {
                    "latency_ms": round(rng.uniform(5, 80), 1),
                    "cost": round(rng.uniform(0.1, 2.0), 2),
                },
                "fallbacks": [f"http://{name}-fb"] if rng.random() < 0.3 else [],
                "tags": [a, b],
            }
        )
    return records


def intent_for(records: list[dict], rng: random.Random, n_services: int = 3) -> str:
    """An intent whose words name a few concrete services' domains/verbs."""
    picks = rng.sample(records, min(n_services, len(records)))
    words: list[str] = []
    for r in picks:
        words.extend(r["tags"])
    return "please " + " then ".join(dict.fromkeys(words))


# ------------------------------------------------------------------- traffic
LOOPS = ("closed", "paced")
INTENTS = ("distinct", "repeat", "session")

MAX_INFLIGHT = 64  # senders of a paced loop: one must be free at every due time

_DEFAULTS = {
    "registry_services": 1000,
    "registry_seed": 7,
    "pool_seed": 21,
    "block": 16,
    "warm_plans": 16,
    "request_timeout_s": 120.0,
    "repeat_share": 0.0,
    "families": 0,
    "rate_per_s": 0.0,
    "trace_seconds": 8.0,
    "origin": "llm",
}


def load_traffic(obj: dict) -> dict:
    """A traffic file's parameters with defaults filled in, checked."""
    unknown = set(obj) - set(_DEFAULTS) - {"loop", "intents", "clients", "why"}
    if unknown:
        raise ValueError(f"traffic file has unknown keys {sorted(unknown)}")
    t = {**_DEFAULTS, **obj}
    if t.get("loop") not in LOOPS:
        raise ValueError(f"traffic 'loop' must be one of {LOOPS}, got {t.get('loop')!r}")
    if t.get("intents") not in INTENTS:
        raise ValueError(f"traffic 'intents' must be one of {INTENTS}, got {t.get('intents')!r}")
    if t["loop"] == "closed" and not (t.get("clients") == "slab_rows" or int(t.get("clients", 0)) > 0):
        raise ValueError("a closed loop needs 'clients' (a count, or \"slab_rows\")")
    if t["loop"] == "paced" and not t["rate_per_s"] > 0:
        raise ValueError("a paced loop needs 'rate_per_s' > 0")
    if t["intents"] == "repeat" and not 0.0 < t["repeat_share"] < 1.0:
        raise ValueError("intents 'repeat' needs 0 < repeat_share < 1")
    if t["intents"] == "session" and not t["families"] > 0:
        raise ValueError("intents 'session' needs 'families' > 0")
    if not int(t["block"]) > 0:
        raise ValueError("'block' must be a positive count")
    return t


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request the generator deals: a fresh intent, or (``repeat``) a
    re-send whose target the loop picks among the intents already answered,
    at the seeded position ``pick`` in [0, 1)."""

    intent: Optional[str]
    pick: float = 0.0

    @property
    def fresh(self) -> bool:
        return self.intent is not None


class Generator:
    """Deals requests in order. ``fresh(j)`` is the j-th fresh intent of the
    run and ``gap(k)`` the k-th inter-arrival gap of a paced schedule; both
    are pure functions of (traffic, seed, index)."""

    def __init__(self, traffic: dict, seed: int) -> None:
        self.traffic = load_traffic(traffic)
        self.seed = int(seed)
        t = self.traffic
        self.registry = build_registry(t["registry_services"], t["registry_seed"])

    def item(self, i: int) -> str:
        """Item i of the traffic file's intent stream; the seed has no say."""
        t = self.traffic
        w = i % t["families"] if t["intents"] == "session" else i
        # str seeds hash stably in random.Random
        wording = intent_for(self.registry, random.Random(f"{t['pool_seed']}:wording:{w}"))
        return f"{wording} for case {i}"

    def _dealt(self, what: str, k: int) -> int:
        """Index into a stream of the k-th item this seed deals from it:
        block k // block, at a seeded position inside the block."""
        n = int(self.traffic["block"])
        b, pos = divmod(k, n)
        order = list(range(n))
        random.Random(f"{self.seed}:{what}:{b}").shuffle(order)
        return b * n + order[pos]

    def fresh(self, j: int) -> str:
        """The j-th fresh intent of the run."""
        return self.item(self._dealt("intent", j))

    def draws(self):
        """The run's request sequence (endless)."""
        share = self.traffic["repeat_share"] if self.traffic["intents"] == "repeat" else 0.0
        rng = random.Random(f"{self.seed}:draw")
        j = 0
        while True:
            if rng.random() < share:
                yield Draw(None, rng.random())
            else:
                yield Draw(self.fresh(j))
                j += 1

    def gap(self, k: int) -> float:
        """Seconds between the (k-1)-th and k-th paced send."""
        t = self.traffic
        i = self._dealt("gap", k)
        return random.Random(f"{t['pool_seed']}:gap:{i}").expovariate(t["rate_per_s"])

    def schedule(self, n: int) -> list[float]:
        """Due times (seconds from the loop's start) of the first ``n`` sends."""
        out, t = [], 0.0
        for k in range(n):
            t += self.gap(k)
            out.append(t)
        return out


# --------------------------------------------------------------------- loops
@dataclasses.dataclass
class Sample:
    """One finished request as the client saw it (``time.monotonic`` s)."""

    t_due: float  # closed: the send instant; paced: the scheduled instant
    t_send: float
    t_done: float
    ok: bool
    fresh: bool
    why: str = ""  # failure reason
    trace_id: str = ""
    gen_late_ms: float = 0.0  # closed: reply-to-next-send gap; paced: send lateness
    intent: str = ""

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_due) * 1e3


# post(intent) -> (ok, why, trace_id); raises nothing.
Post = Callable[[str], tuple[bool, str, str]]


class Loop:
    """Runs a traffic file's loop against ``post`` until ``stop()``.
    Samples accumulate in completion order; ``fresh_done`` counts answered
    fresh intents (what ``warm_plans`` waits on)."""

    def __init__(self, gen: Generator, post_factory: Callable[[], Post], clients: int) -> None:
        self.gen = gen
        self.traffic = gen.traffic
        self.clients = clients
        self._post_factory = post_factory
        self._lock = threading.Lock()
        self._draws = gen.draws()
        self._answered: list[str] = []
        self.samples: list[Sample] = []
        self.fresh_done = 0
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._paced_k = 0
        self._t_next_due = 0.0

    # -- shared by both loops
    def _next_intent(self) -> tuple[str, bool]:
        """Deal the next request under the lock. A repeat drawn before
        anything was answered falls through to the next fresh intent."""
        with self._lock:
            while True:
                d = next(self._draws)
                if d.fresh:
                    return d.intent, True
                if self._answered:
                    return self._answered[int(d.pick * len(self._answered))], False

    def _record(self, s: Sample, intent: str) -> None:
        s.intent = intent
        with self._lock:
            self.samples.append(s)
            if s.ok and s.fresh:
                self._answered.append(intent)
                self.fresh_done += 1

    # -- closed
    def _closed_client(self) -> None:
        post = self._post_factory()
        t_prev_done: Optional[float] = None
        while not self._stop.is_set():
            intent, fresh = self._next_intent()
            t_send = time.monotonic()
            ok, why, trace_id = post(intent)
            t_done = time.monotonic()
            gap_ms = 0.0 if t_prev_done is None else (t_send - t_prev_done) * 1e3
            self._record(
                Sample(t_send, t_send, t_done, ok, fresh, why, trace_id, gap_ms), intent
            )
            t_prev_done = t_done

    # -- paced
    def _paced_worker(self) -> None:
        post = self._post_factory()
        while not self._stop.is_set():
            with self._lock:
                k = self._paced_k
                self._paced_k += 1
                self._t_next_due += self.gen.gap(k)
                t_due = self._t_next_due
            delay = t_due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            intent, fresh = self._next_intent()
            t_send = time.monotonic()
            ok, why, trace_id = post(intent)
            t_done = time.monotonic()
            self._record(
                Sample(t_due, t_send, t_done, ok, fresh, why, trace_id, (t_send - t_due) * 1e3),
                intent,
            )

    def start(self) -> None:
        self._t_next_due = time.monotonic()
        if self.traffic["loop"] == "closed":
            target, n = self._closed_client, self.clients
        else:
            # Each of a pool of senders claims the next due slot in order.
            target, n = self._paced_worker, MAX_INFLIGHT
        for i in range(n):
            th = threading.Thread(target=target, name=f"loadgen-{i}", daemon=True)
            th.start()
            self._threads.append(th)

    def stop(self, join_timeout_s: float) -> bool:
        """Ask every sender to stop after its current request; True when all
        have ended within the timeout."""
        self._stop.set()
        deadline = time.monotonic() + join_timeout_s
        for th in self._threads:
            th.join(max(0.0, deadline - time.monotonic()))
        return not any(th.is_alive() for th in self._threads)

    def snapshot(self) -> list[Sample]:
        with self._lock:
            return list(self.samples)
