"""Training metrics: tokens/sec/chip and MFU as first-class measured outputs.

BASELINE's headline metric is tokens/sec/chip for Llama-3-8B and >=35% MFU on
v5e-16 (SURVEY.md §6); the reference has no metrics at all (its verification
channel is ``kubectl logs`` of ``nvidia-smi``, reference ``README.md:331-335``).
MFU here is *model* FLOPs utilization: analytic model FLOPs per token (from
the model config) — not XLA's executed-FLOPs counter, which would reward
rematerialization for doing extra work.
"""

from __future__ import annotations

import dataclasses
import time

from tpufw.utils.hardware import ChipSpec, detect_chip


@dataclasses.dataclass
class StepMetrics:
    step: int
    loss: float
    step_time_s: float
    tokens_per_sec_per_chip: float
    # None where the device has no peak to divide by (a CPU run).
    mfu: float | None
    # Host time spent waiting on the data iterator BEFORE this step —
    # input-boundness is invisible in step_time (the fetch happens
    # between steps), so it gets its own number.
    data_wait_s: float = 0.0
    # Steps averaged into this entry (sync_every > 1 measures a WINDOW
    # of asynchronously-dispatched steps per host sync; step/loss are
    # the window's last step's).
    window_steps: int = 1

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.mfu is None:
            del d["mfu"]
        return d

    def event_fields(self) -> dict:
        """Fields of the ``step`` telemetry event, rounded for the log."""
        d = self.as_dict()
        for key, digits in (
            ("loss", 6), ("step_time_s", 6), ("data_wait_s", 6),
            ("mfu", 5), ("tokens_per_sec_per_chip", 1),
        ):
            if key in d:
                d[key] = round(d[key], digits)
        return d


class Meter:
    """Accumulates step timings and converts to tokens/sec/chip + MFU.

    ``flops_per_token`` comes from ``config.flops_per_token(seq_len)``;
    ``n_chips`` is the global device count (the denominator that makes
    tokens/sec/chip comparable across slice sizes).
    """

    def __init__(
        self,
        tokens_per_step: int,
        flops_per_token: float,
        n_chips: int,
        chip: ChipSpec | None = None,
        registry=None,
    ):
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.n_chips = max(n_chips, 1)
        # None on a CPU backend: throughput is still metered, MFU is not.
        self.chip = chip or detect_chip()
        self._t0: float | None = None
        # Optional tpufw.obs.Registry: every stop() publishes the
        # window into the shared scrape surface (histograms for the
        # time distributions, gauges for the point-in-time headline).
        self.registry = registry
        if registry is not None:
            self._c_steps = registry.counter(
                "tpufw_train_steps_total", "optimizer steps completed"
            )
            self._c_tokens = registry.counter(
                "tpufw_train_tokens_total", "target tokens trained on"
            )
            self._h_step = registry.histogram(
                "tpufw_train_step_time_seconds",
                "per-step wall time (window average when sync_every > 1)",
            )
            self._h_wait = registry.histogram(
                "tpufw_train_data_wait_seconds",
                "per-step host wait on the input pipeline",
            )
            self._g_step = registry.gauge(
                "tpufw_train_step", "last synced optimizer step"
            )
            self._g_loss = registry.gauge(
                "tpufw_train_loss", "loss at the last synced step"
            )
            if self.chip is not None:
                self._g_mfu = registry.gauge(
                    "tpufw_train_mfu", "model FLOPs utilization (0..1)"
                )
            self._g_tps = registry.gauge(
                "tpufw_train_tokens_per_sec_per_chip",
                "throughput per chip",
            )

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(
        self,
        step: int,
        loss: float,
        data_wait_s: float = 0.0,
        n_steps: int = 1,
    ) -> StepMetrics:
        """``n_steps`` > 1: the elapsed time covers a window of that
        many dispatched steps (one host sync per window); throughput,
        step time, AND data_wait_s (pass the window's summed wait) are
        all attributed per step, so their units stay consistent."""
        if self._t0 is None:
            raise RuntimeError("Meter.stop() without start()")
        # The loss FETCH is the window barrier and must happen before
        # the clock is read: dispatch is asynchronous, and float()
        # blocks until the device has produced the value.
        loss = float(loss)
        n = max(n_steps, 1)
        dt = (time.perf_counter() - self._t0) / n
        data_wait_s = data_wait_s / n
        self._t0 = None
        tps_chip = self.tokens_per_step / dt / self.n_chips
        mfu = (
            None
            if self.chip is None
            else tps_chip * self.flops_per_token / self.chip.peak_bf16_flops
        )
        if self.registry is not None:
            self._c_steps.inc(n)
            self._c_tokens.inc(self.tokens_per_step * n)
            # Per-step averages observed n times: _sum/_count aggregate
            # to the window's exact totals (see Histogram.observe).
            self._h_step.observe(dt, n=n)
            self._h_wait.observe(data_wait_s, n=n)
            self._g_step.set(step)
            self._g_loss.set(loss)
            if mfu is not None:
                self._g_mfu.set(mfu)
            self._g_tps.set(tps_chip)
        return StepMetrics(
            step=step,
            loss=loss,
            step_time_s=dt,
            tokens_per_sec_per_chip=tps_chip,
            mfu=mfu,
            data_wait_s=data_wait_s,
            window_steps=n_steps,
        )


def timed_batches(data):
    """Wrap an iterator, yielding (data_wait_s, batch) — the ONE place
    host blocking on the input pipeline is measured (all three trainer
    loops use it)."""
    it = iter(data)
    while True:
        t0 = time.perf_counter()
        try:
            batch = next(it)
        except StopIteration:
            return
        yield time.perf_counter() - t0, batch
