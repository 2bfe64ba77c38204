"""A closed loop of committee-aggregate verifies through the serve executor,
the keys taken from the program's device-resident pubkey registry.

The node's aggregate queue holds `outstanding` statements at all times, as
in `aggregate_verify`, but each statement is what gossip carries: the
committee's slot and index, its aggregation bits and the signature.  Set-up
makes the registry (2**20 keys) and one epoch's committees from the seed,
uploads them into `bls_batch.registry.PubkeyRegistry` (limbs converted on
the device), and warms the two programs of a batch.  The loop submits
through `ServeExecutor.submit_committee_aggregate_verify`: the host parses
the signature only, and each batch runs the committee aggregation program,
then the RLC kernel on its output.

`correct` counts, as in `aggregate_verify`, the statements never answered
and the verdicts that differ from the plain reference
(`reference.committees`: FastAggregateVerify over the members the bits
name, the keys added by the reference itself): false verdicts in the
window, a sample of the answered statements drawn from the seed, and the
probe, four full batches each tampered in one half (a flipped bit, the
neighbouring committee, swapped signatures) that go through the window's
two programs and have to be refused.  `registry_errors` counts registry
keys, drawn from the seed, that the device holds otherwise than a scalar
multiplication computes them.

The program's registry is needed from the start: a program without it
fails at once.
"""

from __future__ import annotations

import importlib.util
import random
import time
from collections import deque

import numpy as np

from ..harness import log
from ..reference import committees
from .aggregate_verify import System as _AggregateVerify
from .aggregate_verify import _UnitCoefficients, _verdict

REGISTRY_MODULE = "consensus_specs_tpu.ops.bls_batch.registry"


class System(_AggregateVerify):
    # all_bits: the aggregation sums the whole committee, its bits
    # ignored; unit_coefficients: the batch check with every random
    # coefficient 1; altered_registry: one key changed on the device
    # after the upload
    CONTROLS = ("all_bits", "unit_coefficients", "altered_registry")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, span, control: str | None = None):
        if importlib.util.find_spec(REGISTRY_MODULE) is None:
            raise RuntimeError(f"the program has no {REGISTRY_MODULE}: it "
                               f"cannot hold the keys this cell aggregates")
        super().__init__(config, traffic, seed, seconds, span, control)
        self.executor_options = {}

    # --- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        """The registry, the committees, the statement pool and the probe,
        drawn from the seed before JAX starts."""
        c, t = self.config, self.traffic
        per = self.per_message
        t0 = time.perf_counter()
        self.coords = committees.make_registry(
            self.seed, int(c["validators"]), workers=self.workers)
        t1 = time.perf_counter()
        self.table = committees.make_committees(
            self.seed, int(c["validators"]), int(c["slots_per_epoch"]),
            int(c["committees_per_slot"]), int(c["committee_size"]))
        per_slot = self.table.shape[1]
        counts = [-(-int(t[k]) // per) for k in
                  ("pool_statements", "warmup_statements", "probe_statements")]
        if sum(counts) > self.table.shape[0] * per_slot:
            raise ValueError("the traffic needs more committees than an "
                             "epoch has")
        made = committees.make_statements(
            self.seed, self.table,
            [(k // per_slot, k % per_slot) for k in range(sum(counts))],
            per, float(t["p_attest"]), float(t["p_miss"]),
            workers=self.workers)
        p, w = counts[0] * per, (counts[0] + counts[1]) * per
        self.pool, self.warmup = made[:p], made[p:w]
        base = made[w:w + int(t["probe_statements"])]
        rng = random.Random(f"{self.seed}/probe")
        self.probes = [committees.tamper(base, pr["tamper"], pr["half"], rng,
                                         per_slot) for pr in t["probe"]]
        keys = [sum(committees.decode_bitlist(s[2])) for s in self.pool]
        log(f"registry: {len(self.coords)} keys made in {t1 - t0:.3f} s; "
            f"statement pool: {len(self.pool)} statements of "
            f"{sum(keys) / len(keys):.1f} keys each, {len(self.warmup)} for "
            f"warm-up, {len(base)} for the probe, made in "
            f"{time.perf_counter() - t1:.3f} s by {self.workers} processes")

    def setup(self, jax, devices) -> None:
        from consensus_specs_tpu.ops import bls_batch
        from consensus_specs_tpu.ops.bls_batch.registry import PubkeyRegistry
        from consensus_specs_tpu.serve import ServeExecutor

        t0 = time.perf_counter()
        self.registry = PubkeyRegistry(self.coords, self.table)
        jax.block_until_ready((self.registry.x, self.registry.y))
        log(f"registry upload and limb conversion: "
            f"{time.perf_counter() - t0:.3f} s")
        if self.control == "altered_registry":
            i = int(self.table[0, 0][np.flatnonzero(
                committees.decode_bitlist(self.pool[0][2]))[0]])
            j = (i + 1) % len(self.coords)
            self.registry.x = self.registry.x.at[i].set(self.registry.x[j])
            self.registry.y = self.registry.y.at[i].set(self.registry.y[j])
        self.bls_batch = bls_batch
        self.real_kernel = real = bls_batch.batch_verify_async
        if self.control == "unit_coefficients":
            bls_batch.batch_verify_async = (
                lambda tasks, rng=None, **kw:
                real(tasks, rng=_UnitCoefficients(), **kw))
        elif self.control == "all_bits":
            def kernel(tasks, rng=None, pubkeys=None, **kw):
                if pubkeys is not None:
                    pubkeys.bits = [np.ones_like(b) for b in pubkeys.bits]
                return real(tasks, rng=rng, pubkeys=pubkeys, **kw)
            bls_batch.batch_verify_async = kernel
        # warm the two programs of the loop's one rung, a full batch of
        # max_batch (or of the whole backlog, where that is smaller), from
        # the few warm-up statements
        warm_ex = ServeExecutor(registry=self.registry)
        rung = min(warm_ex.max_batch, self.outstanding_target)
        warm = [warm_ex.submit_committee_aggregate_verify(
            *self.warmup[i % len(self.warmup)]) for i in range(rung)]
        warm_ex.drain()
        if self.control is None and not all(f.result() for f in warm):
            raise RuntimeError("a valid warm-up statement was refused")
        self.ex = ServeExecutor(registry=self.registry)
        if len(self.probes[0][0]) != rung:
            raise ValueError(f"probe_statements is {len(self.probes[0][0])}; "
                             f"the window's batches hold {rung}")
        # bytes one batch of the aggregation program has to move: the
        # gathered keys and committee rows, the ids and bits in, the
        # affine sums and their flags out
        limb = self.registry.x.shape[1] * self.registry.x.dtype.itemsize
        size = self.registry.size
        self.aggregate_bytes = rung * (size * (2 * limb + 4 + 1) + 4
                                       + 2 * limb + 1)
        self.stats0 = self.ex.stats()
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
            with self.span("bench.submit"):
                fut = self.ex.submit_committee_aggregate_verify(
                    *self.pool[i % len(self.pool)])
            self.sent.append(fut)
            self.queue.append(fut)

    def counters(self) -> dict:
        out = super().counters()
        out["keys_aggregated"] = (self.stats1["keys_aggregated"]
                                  - self.stats0["keys_aggregated"])
        out["pk_aggregate_bytes_per_batch"] = self.aggregate_bytes
        return out

    # --- correctness --------------------------------------------------------

    def _probe_verdicts(self) -> list:
        """The two programs' verdict on each probe batch, its statements
        parsed as the executor's committee submit parses them."""
        from consensus_specs_tpu.ops.bls.ciphersuite import _sig_to_point
        from consensus_specs_tpu.ops.bls_batch.registry import decode_bitlist

        reg = self.registry
        sigs = {}
        out = []
        for batch, _ in self.probes:
            tasks, ids, bits = [], [], []
            for slot, index, bitlist, msg, sig in batch:
                if sig not in sigs:
                    try:
                        sigs[sig] = _sig_to_point(sig)
                    except ValueError:
                        sigs[sig] = None
                cid = reg.committee_id(slot, index)
                b = decode_bitlist(bitlist, reg.size)
                if cid is None or b is None or not b.any() \
                        or sigs[sig] is None:
                    break           # the executor refuses it at submit
                tasks.append((None, msg, sigs[sig]))
                ids.append(cid)
                bits.append(b)
            if len(tasks) < len(batch):
                out.append(False)
                continue
            out.append(bool(self.bls_batch.batch_verify_async(
                tasks, block=False, pubkeys=reg.select(ids, bits)).result()))
        return out

    def _check(self) -> dict:
        verdicts = [_verdict(fut) for fut in self.sent]
        unanswered = sum(v is None for v in verdicts)
        # every statement of the pool was built valid
        false_in_window = sum(v is False for v in verdicts)
        answered = [i for i, v in enumerate(verdicts) if v is not None]
        rng = random.Random(f"{self.seed}/reference-sample")
        sample = rng.sample(answered, min(int(self.traffic[
            "reference_sample"]), len(answered)))
        keys = random.Random(f"{self.seed}/registry-sample").sample(
            range(len(self.coords)), int(self.traffic["registry_sample"]))
        read = self.registry.read_back(keys)
        tampered = [[batch[j] for j in where] for batch, where in self.probes]

        def during():
            return (self._probe_verdicts(),
                    committees.check_registry(self.seed, keys, read))

        refs, (probe, registry_errors) = committees.verify_all(
            [self.pool[i % len(self.pool)] for i in sample]
            + [s for group in tampered for s in group],
            self.table, self.coords, self.workers, during=during)
        sample_disagree = sum(refs[k] != verdicts[i]
                              for k, i in enumerate(sample))
        refs = refs[len(sample):]
        for group in tampered:
            ok, refs = refs[:len(group)], refs[len(group):]
            if all(ok):
                raise RuntimeError("the reference accepts a tampered probe "
                                   "statement: the probe proves nothing")
        probe_disagree = sum(probe)         # each probe batch must be refused
        errors = false_in_window + sample_disagree + probe_disagree
        return {
            "attempted": len(self.sent) + len(self.probes),
            "failed": unanswered + false_in_window + probe_disagree,
            "checks": {"unanswered": (unanswered, 0),
                       "verdict_errors": (errors, 0),
                       "registry_errors": (registry_errors, 0)},
            "notes": {"false_in_window": false_in_window,
                      "reference_sample": len(sample),
                      "sample_disagreements": sample_disagree,
                      "registry_sample": len(keys),
                      "probe_batches": len(probe),
                      "probe_accepted": sum(probe),
                      "pool_cycled": self.cycled},
        }
