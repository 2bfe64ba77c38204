"""A closed loop of FastAggregateVerify requests through the serve executor.

The node's aggregate queue holds `outstanding` statements at all times: set-up
submits that many, and each time a batch of answers settles the loop submits
as many new ones, in wire format, through
`ServeExecutor.submit_fast_aggregate_verify` (parse, decompression and
subgroup checks on the host), then `pump()`s them to the RLC batch kernel.
When the window closes the loop stops refilling and `drain()`s the rest.

`correct` counts the statements submitted and never answered, and the
verdicts that differ from the plain reference (`reference.bls`): every
false verdict in the window (each statement was built valid), every
disagreement on a sample of the answered statements drawn from the seed,
and every disagreement on the probe.  The probe is what gives the check
teeth against a kernel that accepts without looking: once the window has
closed, full batches of the window's size (`probe_statements`, the 512
rung), each with one or two statements tampered in one half, go through
the RLC kernel the window drove (`bls_batch.batch_verify_async`, as the
executor calls it), and each has to be refused.  The executor itself would
answer such a batch by rechecking its 512 statements one pairing at a
time, a program the window never runs, so the probe reads the kernel's
batch verdict.
"""

from __future__ import annotations

import os
import random
import time
from collections import deque

from ..harness import log
from ..reference import statements

SETTLE_GRACE_S = 60.0
# the deadline_shed control's deadline, one a node might give an aggregate
SHED_DEADLINE_MS = 4000.0


class _UnitCoefficients(random.Random):
    """Draws 0 bits, so every RLC coefficient is 0 | 1 = 1."""

    def getrandbits(self, k: int) -> int:
        return 0


def _verdict(fut):
    """The answer a handle settled to, or None if it has none."""
    if not fut.done() or fut.exception() is not None:
        return None
    return fut.result()


class System:
    # The RLC kernel runs some 4 * 10**6 device ops per 512-statement
    # batch, past the profiler's 2 GB limit within the first second of
    # the first batch: trace the host only, where the TPU runtime marks
    # each program run
    TPU_TRACE_MODE = "TRACE_ONLY_HOST"
    # unit_coefficients: the program's batch check with every random
    # coefficient 1 (its `rng` argument), the naive aggregate check;
    # deadline_shed: the executor's own shedding armed, which breaks "no
    # request is dropped"
    CONTROLS = ("unit_coefficients", "deadline_shed")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, span, control: str | None = None):
        if control not in (None,) + self.CONTROLS:
            raise ValueError(f"no control {control!r}")
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.span = seed, seconds, span
        self.control = control
        self.executor_options = ({"deadline_ms": SHED_DEADLINE_MS}
                                 if control == "deadline_shed" else {})
        self.per_message = int(traffic["statements_per_message"])
        self.outstanding_target = int(traffic["outstanding"])
        self.workers = min(int(traffic["workers"]), os.cpu_count() or 1)

    # --- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        """The statement pool and the probe, drawn from the seed before
        JAX starts."""
        t = self.traffic
        per = self.per_message
        counts = [-(-int(t[k]) // per) for k in
                  ("warmup_statements", "pool_statements", "probe_statements")]
        t0 = time.perf_counter()
        made = statements.make_statements(self.seed, sum(counts), per,
                                          workers=self.workers)
        w, p = counts[0] * per, (counts[0] + counts[1]) * per
        self.warmup, self.pool = made[:w], made[w:p]
        base = made[p:p + int(t["probe_statements"])]
        rng = random.Random(f"{self.seed}/probe")
        self.probes = [statements.tamper(base, pr["tamper"], pr["half"], rng)
                       for pr in t["probe"]]
        log(f"statement pool: {len(self.pool)} statements, "
            f"{len(self.warmup)} for warm-up, {len(base)} for the probe, "
            f"made in {time.perf_counter() - t0:.3f} s by {self.workers} "
            f"processes")

    def setup(self, jax, devices) -> None:
        from consensus_specs_tpu.ops import bls_batch
        from consensus_specs_tpu.ops.bls.ciphersuite import (
            parse_fast_aggregate_task)
        from consensus_specs_tpu.serve import ServeExecutor

        self.bls_batch = bls_batch
        self.real_kernel = bls_batch.batch_verify_async
        if self.control == "unit_coefficients":
            real = self.real_kernel
            bls_batch.batch_verify_async = (
                lambda tasks, rng=None, **kw:
                real(tasks, rng=_UnitCoefficients(), **kw))
        # warm the one rung the loop uses, a full batch of max_batch, from
        # the few warm-up statements parsed once each
        warm_ex = ServeExecutor()
        tasks = [parse_fast_aggregate_task([pk], msg, sig)
                 for pk, msg, sig in self.warmup]
        warm = [warm_ex.submit_verify_task(tasks[i % len(tasks)])
                for i in range(warm_ex.max_batch)]
        warm_ex.drain()
        if not all(f.result() for f in warm):
            raise RuntimeError("a valid warm-up statement was refused")
        self.ex = ServeExecutor(**self.executor_options)
        rung = min(self.ex.max_batch, self.outstanding_target)
        if len(self.probes[0][0]) != rung:
            raise ValueError(f"probe_statements is {len(self.probes[0][0])}; "
                             f"the window's batches hold {rung}")
        self.stats0 = self.ex.stats()
        # the backlog the window starts from
        self.sent = []      # handle i answers pool statement i (mod its size)
        self.queue = deque()
        self.cycled = False
        self._submit(self.outstanding_target)

    def _submit(self, n: int) -> None:
        for _ in range(n):
            i = len(self.sent)
            if i >= len(self.pool) and not self.cycled:
                self.cycled = True
                log(f"the statement pool of {len(self.pool)} is exhausted: "
                    f"statements repeat from here on")
            pk, msg, sig = self.pool[i % len(self.pool)]
            with self.span("bench.submit"):
                fut = self.ex.submit_fast_aggregate_verify([pk], msg, sig)
            self.sent.append(fut)
            self.queue.append(fut)

    # --- window -------------------------------------------------------------

    def window(self, tracer) -> dict:
        from consensus_specs_tpu.serve.futures import FutureError

        settles = []               # (time, statements answered)
        t0 = time.perf_counter()
        with self.span("bench.pump"):
            self.ex.pump()
        while True:
            with self.span("bench.wait"):
                try:
                    self.queue[0].exception()     # settles its batch
                except FutureError:
                    self.queue.popleft()          # can never settle
            t = time.perf_counter()
            answered = 0
            while self.queue and self.queue[0].done():
                answered += self.queue.popleft().exception() is None
            settles.append((t, answered))
            tracer.poll()
            if t - t0 >= self.seconds:
                break
            self._submit(self.outstanding_target - len(self.queue))
            with self.span("bench.pump"):
                self.ex.pump()
        inside = [(t, n) for t, n in settles if t - t0 <= self.seconds and n]
        self.answered_in_window = sum(n for _, n in inside)
        if len(inside) < 2:
            log(f"{len(inside)} batches answered inside the window: the "
                f"rate is taken over the whole window")
            return {"verifies_per_s": self.answered_in_window / self.seconds}
        return {"verifies_per_s": sum(n for _, n in inside[1:])
                / (inside[-1][0] - inside[0][0])}

    def release(self) -> None:
        """Answer what is still outstanding (a minute at most)."""
        from consensus_specs_tpu.serve.futures import FutureError

        deadline = time.perf_counter() + SETTLE_GRACE_S
        for fut in self.queue:
            try:
                fut.exception(timeout=max(1e-3,
                                          deadline - time.perf_counter()))
            except FutureError:
                continue        # left unanswered: the check counts it
        self.stats1 = self.ex.stats()
        del self.ex

    def counters(self) -> dict:
        s0, s1 = self.stats0, self.stats1
        return {"settled": s1["settled"] - s0["settled"],
                "batches": s1["batches"] - s0["batches"],
                "submitted": len(self.sent),
                "answered_in_window": self.answered_in_window}

    # --- correctness --------------------------------------------------------

    def _probe_verdicts(self) -> list:
        """The RLC kernel's verdict on each probe batch, its statements
        parsed as the executor's submit parses them."""
        from consensus_specs_tpu.ops.bls.ciphersuite import (
            parse_fast_aggregate_task)

        parsed = {}
        out = []
        for batch, _ in self.probes:
            for s in batch:
                if s not in parsed:
                    parsed[s] = parse_fast_aggregate_task([s[0]], s[1], s[2])
            tasks = [parsed[s] for s in batch]
            if any(t is None for t in tasks):
                out.append(False)       # the executor refuses it at submit
                continue
            out.append(bool(self.bls_batch.batch_verify_async(
                tasks, block=False).result()))
        return out

    def check(self) -> dict:
        try:
            return self._check()
        finally:
            self.bls_batch.batch_verify_async = self.real_kernel

    def _check(self) -> dict:
        verdicts = [_verdict(fut) for fut in self.sent]
        unanswered = sum(v is None for v in verdicts)
        # every statement of the pool was built valid
        false_in_window = sum(v is False for v in verdicts)
        answered = [i for i, v in enumerate(verdicts) if v is not None]
        rng = random.Random(f"{self.seed}/reference-sample")
        sample = rng.sample(answered, min(int(self.traffic[
            "reference_sample"]), len(answered)))
        tampered = [[batch[j] for j in where] for batch, where in self.probes]
        refs, probe = statements.verify_all(
            [self.pool[i % len(self.pool)] for i in sample]
            + [s for group in tampered for s in group],
            self.workers, during=self._probe_verdicts)
        sample_disagree = sum(refs[k] != verdicts[i]
                              for k, i in enumerate(sample))
        refs = refs[len(sample):]
        probe_want = []
        for group in tampered:
            ok, refs = refs[:len(group)], refs[len(group):]
            if all(ok):
                raise RuntimeError("the reference accepts a tampered probe "
                                   "statement: the probe proves nothing")
            probe_want.append(False)
        probe_disagree = sum(g != w for g, w in zip(probe, probe_want))
        errors = false_in_window + sample_disagree + probe_disagree
        return {
            "attempted": len(self.sent) + len(self.probes),
            "failed": unanswered + false_in_window + probe_disagree,
            "checks": {"unanswered": (unanswered, 0),
                       "verdict_errors": (errors, 0)},
            "notes": {"false_in_window": false_in_window,
                      "reference_sample": len(sample),
                      "sample_disagreements": sample_disagree,
                      "probe_batches": len(probe),
                      "probe_accepted": sum(probe),
                      "pool_cycled": self.cycled},
        }
