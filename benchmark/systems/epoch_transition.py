"""Back-to-back epoch transitions over a registry resident on the device.

The window drives the program's one-call step,
`parallel.make_sharded_epoch_step(parallel.make_mesh(chips), params)`:
the phase0 sweep, the balances root and the registry root.  Each step's
balances and effective balances feed the next, the epoch goes up by one,
and after each step the host fetches both 32-byte roots, as a node needs
them for the state root.

`correct` compares the balances and effective balances the last step left,
and both roots of a sample of the window's steps drawn from the seed, with
`reference.epoch` chained through every step the window ran.
"""

from __future__ import annotations

import time

import numpy as np

from ..reference import epoch as ref

SCOPES = {
    "sweep": ("cst.epoch_sweep",),
    "merkle": ("cst.balances_list_root", "cst.validator_records_root",
               "cst.validator_registry_root"),
}


def sweep_bytes(n: int) -> int:
    """Bytes the sweep has to move for n validators: it reads balance,
    effective balance, the four epochs and the inclusion delay (8 B each),
    the proposer index (4 B) and four flags (1 B each), and writes the new
    balance and effective balance (8 B each)."""
    return n * (7 * 8 + 4 + 4 * 1 + 2 * 8)


def float32_control(step, preset: dict):
    """The control: the program's step with the balances and effective
    balances it hands on replaced by the float32 sweep's, on the device."""
    import jax
    import jax.numpy as jnp

    sweep32 = jax.jit(lambda reg: ref.sweep_float32(reg, 0, 0, 0, preset,
                                                    xp=jnp))

    def control(reg, sc, *static):
        _, _, bal_root, reg_root = step(reg, sc, *static)
        bal, eff = sweep32(reg)
        return bal, eff, bal_root, reg_root

    return control


class System:
    # a step runs some 10**5 device ops: trace the first few seconds only
    TRACE_SECONDS = 4.0
    # float32_sweep: the sweep in float32 lanes, native on the chip, in
    # place of the uint64 one the configuration states
    CONTROLS = ("float32_sweep",)

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, span, control: str | None = None):
        if control not in (None,) + self.CONTROLS:
            raise ValueError(f"no control {control!r}")
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.span = seed, seconds, span
        self.control = control
        self.n = int(config["validators"])
        self.preset = config["preset"]
        self.finality_delay = int(config["registry"]["finality_delay"])
        self.start_epoch = int(config["registry"]["start_epoch"])

    # --- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        self.reg, self.pubkey_root, self.credentials, self.slashings_sum = (
            ref.make_registry(self.n, self.seed, self.preset,
                              self.config["registry"]))

    def setup(self, jax, devices) -> None:
        from consensus_specs_tpu import parallel

        p = self.preset
        params = parallel.EpochParams(
            base_reward_factor=p["BASE_REWARD_FACTOR"],
            base_rewards_per_epoch=p["BASE_REWARDS_PER_EPOCH"],
            proposer_reward_quotient=p["PROPOSER_REWARD_QUOTIENT"],
            inactivity_penalty_quotient=p["INACTIVITY_PENALTY_QUOTIENT"],
            min_epochs_to_inactivity_penalty=p[
                "MIN_EPOCHS_TO_INACTIVITY_PENALTY"],
            effective_balance_increment=p["EFFECTIVE_BALANCE_INCREMENT"],
            max_effective_balance=p["MAX_EFFECTIVE_BALANCE"],
            hysteresis_quotient=p["HYSTERESIS_QUOTIENT"],
            hysteresis_downward_multiplier=p["HYSTERESIS_DOWNWARD_MULTIPLIER"],
            hysteresis_upward_multiplier=p["HYSTERESIS_UPWARD_MULTIPLIER"],
            epochs_per_slashings_vector=p["EPOCHS_PER_SLASHINGS_VECTOR"],
            proportional_slashing_multiplier=p[
                "PROPORTIONAL_SLASHING_MULTIPLIER"])
        self.jax = jax
        self.parallel = parallel
        self.step = parallel.make_sharded_epoch_step(
            parallel.make_mesh(len(devices)), params)
        if self.control == "float32_sweep":
            self.step = float32_control(self.step, self.preset)
        self.reg0 = jax.device_put(parallel.RegistryArrays(*self.reg))
        self.static = jax.device_put(
            (np.uint64(self.n), self.pubkey_root, self.credentials))
        # warm-up: two chained steps, as the window starts them, so that
        # the program is built for the placement of its own outputs too
        reg, _ = self._one(self.reg0, self.start_epoch)
        self._one(reg, self.start_epoch + 1)

    def _one(self, reg, epoch: int):
        sc = self.parallel.EpochScalars(
            current_epoch=np.uint64(epoch),
            finality_delay=np.uint64(self.finality_delay),
            slashings_sum=np.uint64(self.slashings_sum))
        bal, eff, bal_root, reg_root = self.step(reg, sc, *self.static)
        roots = (np.asarray(bal_root).astype(">u4").tobytes(),
                 np.asarray(reg_root).astype(">u4").tobytes())
        return reg._replace(balance=bal, effective_balance=eff), roots

    def program_text(self) -> str:
        """The compiled step's HLO text, which names each op's scope."""
        sc = self.parallel.EpochScalars(*(np.uint64(0),) * 3)
        return self.step.lower(self.reg0, sc, *self.static).compile().as_text()

    # --- window -------------------------------------------------------------

    def window(self, tracer) -> dict:
        reg = self.reg0
        self.roots = []
        t0 = time.perf_counter()
        while True:
            with self.span("bench.step"):
                reg, roots = self._one(reg, self.start_epoch + len(self.roots))
            self.roots.append(roots)
            tracer.poll()
            t1 = time.perf_counter()
            if t1 - t0 >= self.seconds:
                break
        self.last = reg
        self.steps = len(self.roots)
        return {"epoch_s": (t1 - t0) / self.steps}

    def counters(self) -> dict:
        return {"steps": self.steps, "validators": self.n,
                "sweep_bytes_per_step": sweep_bytes(self.n),
                "scopes": SCOPES}

    def release(self) -> None:
        """Fetch what the check needs, then free the device state."""
        self.final = (np.asarray(self.last.balance),
                      np.asarray(self.last.effective_balance))
        del self.last, self.reg0, self.static

    # --- correctness --------------------------------------------------------

    def check(self) -> dict:
        """Chain the reference through the window's steps and compare."""
        n_sample = int(self.traffic["checked_root_steps"])
        rng = np.random.default_rng([self.seed, 1])
        sampled = set(rng.choice(self.steps, min(n_sample, self.steps),
                                 replace=False).tolist())
        sampled.add(self.steps - 1)
        registry_roots = ref.RegistryRoots(self.pubkey_root, self.credentials,
                                           self.reg)
        cur = self.reg
        root_mismatches = bad_steps = 0
        for i in range(self.steps):
            bal, eff = ref.sweep(cur, self.start_epoch + i,
                                 self.finality_delay, self.slashings_sum,
                                 self.preset)
            cur = cur._replace(balance=bal, effective_balance=eff)
            if i in sampled:
                want = (ref.balances_root(bal), registry_roots.root(eff))
                wrong = sum(g != w for g, w in zip(self.roots[i], want))
                root_mismatches += wrong
                bad_steps += wrong > 0
        got_bal, got_eff = self.final
        bal_wrong = int(np.count_nonzero(got_bal != cur.balance))
        eff_wrong = int(np.count_nonzero(got_eff != cur.effective_balance))
        return {
            "attempted": self.steps,
            "failed": max(bad_steps, int(bal_wrong + eff_wrong > 0)),
            "checks": {
                "balance_mismatches": (bal_wrong, 0),
                "effective_balance_mismatches": (eff_wrong, 0),
                "root_mismatches": (root_mismatches, 0),
            },
            "notes": {"roots_compared": 2 * len(sampled)},
        }
