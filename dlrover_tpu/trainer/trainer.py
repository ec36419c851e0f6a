"""Trainer: the high-level training loop (AtorchTrainer analogue).

Equivalent capability: reference atorch/atorch/trainer/atorch_trainer.py:129
(`AtorchTrainer` — an HF-Trainer-like loop wiring auto_accelerate, flash
checkpoint save/restore, logging/metrics, and elastic data) with args
dataclass atorch_args.py.

TPU redesign: the loop is functional — state in, state out of a jitted,
GSPMD-sharded train step produced by auto_accelerate; checkpointing is
the flash engine (async HBM->shm with storage persist); progress flows to
the agent/master via write_runtime_metrics + the shm timing ring.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Any, Callable, Iterable, Optional

from dlrover_tpu.common import backend, flight, telemetry, tracing
from dlrover_tpu.common.chaos import chaos_point
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.accelerate import auto_accelerate
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger(__name__)

# The step clock: after dispatching step k the loop waits for the loss
# of step k - STEP_LAG, so the device always has the next step queued
# while the host sees every completion as it happens. 1 is enough while
# the host needs less than a step's time for its own work between two
# dispatches (PERF.md section 6, PR 26, has the chip readings).
STEP_LAG = 1


@dataclasses.dataclass
class TrainingArgs:
    """Reference atorch_args.py analogue, TPU fields."""

    output_dir: str = "/tmp/dlrover_tpu/output"
    max_steps: int = 0               # 0 = run the data out
    num_epochs: int = 1
    micro_batch_size: int = 8
    grad_accum: int = 1
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    optimizer: str = "adamw"         # adamw | sgd | agd | adam8bit
    strategy: Optional[Strategy] = None
    # None = keep the strategy's compute dtype (default bfloat16)
    compute_dtype: Optional[str] = None
    seed: int = 0
    # checkpointing
    flash_checkpoint: bool = True
    save_steps: int = 0              # 0 = only at end
    save_storage_every: int = 1      # persist every Nth shm save
    # adopt the master brain's goodput-aware checkpoint cadence
    # (``ckpt_save_steps`` on the run-config channel): save_steps
    # becomes a control variable the brain moves toward the Young/Daly
    # optimum. Only active when cadence saving is already on
    # (save_steps > 0) and a master is reachable; bounds live with the
    # brain (master side), the trainer adopts what it is handed.
    adopt_cadence: bool = True
    # logging/eval
    log_steps: int = 10
    eval_steps: int = 0
    # the caller's count of model FLOPs per TOKEN for the live
    # ``train.mfu`` gauge; 0 = common/mfu.py's dense estimate,
    # 6 * param_count
    model_flops_per_token: float = 0.0


def _build_optimizer(args: TrainingArgs):
    import optax

    lr = args.learning_rate
    if args.optimizer == "sgd":
        return optax.sgd(lr)
    if args.optimizer == "agd":
        from dlrover_tpu.optimizers import agd

        return agd(lr, weight_decay=args.weight_decay)
    if args.optimizer == "adam8bit":
        from dlrover_tpu.optimizers import adam8bit

        return adam8bit(lr, weight_decay=args.weight_decay)
    return optax.adamw(lr, weight_decay=args.weight_decay)


class Trainer:
    """Train a (loss_fn, init_fn) model over a batch iterable.

    ``train_data``: an iterable of host batches (re-iterable for multi-
    epoch), e.g. an :class:`~dlrover_tpu.trainer.elastic.ElasticDataLoader`.
    Each batch feeds ``loss_fn(params, batch, rng)``.

    ``prestep``: optional host-side hook ``(state, batch) -> (state,
    batch)`` run before every jitted step — the integration point for
    dynamic-embedding batch preparation (e.g.
    :class:`~dlrover_tpu.models.recsys.TieredBatchPreparer`, which
    promotes/demotes TieredKvEmbedding rows so the compiled step only
    ever sees device-resident slots). It runs for eval batches too. A
    hook exposing ``state_dict``/``load_state_dict`` is checkpointed in
    a sidecar next to the engine checkpoint and restored on resume —
    without it a restarted job would pair the restored table with an
    empty id -> slot mapper and silently scramble the embeddings.
    """

    @tracing.start_leg("start.trainer_init")
    def __init__(
        self,
        loss_fn: Callable,
        init_fn: Callable,
        param_logical_axes: Any,
        args: TrainingArgs,
        train_data: Iterable,
        eval_data: Optional[Iterable] = None,
        eval_fn: Optional[Callable] = None,
        optimizer=None,
        prestep: Optional[Callable] = None,
        reshape_channel=None,
        reshape_devices_fn: Optional[Callable] = None,
    ):
        self.args = args
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.param_logical_axes = param_logical_axes
        self.train_data = train_data
        self.eval_data = eval_data
        self.eval_fn = eval_fn or loss_fn
        self.prestep = prestep
        self._prestep_accepts_count = False
        if prestep is not None:
            import inspect

            try:
                self._prestep_accepts_count = (
                    "count" in inspect.signature(prestep).parameters
                )
            except (TypeError, ValueError):
                pass
        self.optimizer = optimizer or _build_optimizer(args)
        strategy = args.strategy or Strategy()
        overrides = dict(
            grad_accum=max(args.grad_accum, strategy.grad_accum),
        )
        if args.compute_dtype is not None:
            overrides["compute_dtype"] = args.compute_dtype
        strategy = dataclasses.replace(strategy, **overrides)
        self._accel = self._accelerate(strategy)
        self.state = self._accel.state
        self.global_step = 0
        # first train_step of this process incarnation traces+compiles;
        # its wall time is attributed to the "compile" goodput category
        self._compiled_once = False
        # live MFU/HBM accounting: FLOPs-per-token computed once per
        # (re)shape (_refresh_flops re-runs in _adopt_accel), device
        # memory_stats availability probed once on first use
        self._flops_per_token = 0.0
        self._peak_flops: float | None = None
        self._device_mem_ok: bool | None = None
        # tokens of one step's batch, counted on the first batch after
        # a (re)shape (None = not counted yet)
        self._tokens_per_step: int | None = None
        self._refresh_flops()
        # the step clock: steps dispatched and not yet seen to complete,
        # oldest first, as (number, its loss on the device, the wall ns
        # its dispatch began at, steady); the wall ns of the last
        # completion seen; the number of the last completed step
        self._pending: collections.deque = collections.deque()
        self._last_done_ns = 0
        self._completed_step = 0
        # step the on-disk pending/latest prestep sidecar was last
        # serialized at (skip-rewrite cache; None = dirty)
        self._prestep_sidecar_step = None
        # the master client for the log-boundary advisories (brain
        # cadence adoption, in-band hardware re-probe), probed lazily
        # on the first log boundary (None = unprobed, False = no master)
        self._advisory_client = None
        # in-band hardware re-probe: this process holds the chip, so
        # the device legs can only run here (agent/probe.py). Armed a
        # full interval out — the agent's join-time probe just ran.
        from dlrover_tpu.agent import probe as hw_probe

        self._reprobe = None
        if not hw_probe.probe_disabled():
            self._reprobe = hw_probe.default_scheduler()
            self._reprobe.seed({"elapsed_s": 0.0})
        self._engine = None
        if args.flash_checkpoint:
            from dlrover_tpu.trainer.flash_checkpoint.engine import (
                ShardedCheckpointEngine,
            )

            self._engine = ShardedCheckpointEngine(
                os.path.join(args.output_dir, "checkpoints")
            )
        # restart-free elasticity: when the agent exports a reshape
        # channel (NodeEnv.RESHAPE_DIR) — or a test passes one — the
        # train loop polls it at every step boundary and adopts
        # membership changes IN PROCESS (mesh rebuild + device-to-
        # device reshard) instead of being restarted.
        self._reshape_channel = reshape_channel
        self._reshape_devices_fn = reshape_devices_fn
        self._reshape_round = -1
        if self._reshape_channel is None:
            from dlrover_tpu.common.constants import NodeEnv

            rdir = os.environ.get(NodeEnv.RESHAPE_DIR, "")
            if rdir:
                from dlrover_tpu.trainer.elastic.reshape import (
                    ReshapeChannel,
                )

                self._reshape_channel = ReshapeChannel(rdir)
        if self._reshape_channel is not None:
            # advertise the watcher: only now will the agent signal a
            # reshape instead of restarting this worker
            self._reshape_channel.mark_ready()
        self._timer = None
        try:
            from dlrover_tpu.trainer.timer import get_step_timer

            self._timer = get_step_timer()
        except Exception:  # noqa: BLE001 - shm unavailable (bare env)
            pass
        # always-on device-time accounting + deep-capture execution
        # (common/profiling.py): one sampled step every
        # DLROVER_PROF_SAMPLE_STEPS becomes device.optime_ms gauges +
        # the persisted op-cost baseline; the agent's capture channel
        # (DLROVER_PROF_CAPTURE_DIR) is polled at every step boundary.
        # Self-disabling where no parse toolchain exists — the hooks
        # then cost one branch per step.
        from dlrover_tpu.common import profiling

        self._prof = profiling.DeviceTimeSampler(
            os.path.join(args.output_dir, "prof"),
        )
        self._refresh_prof_context()

    # -------------------------------------------------------------- resume

    _DATA_STATE_BYTES = 4096

    def _pack_data_state(self):
        """Dataloader/sampler progress as a fixed-size JSON leaf so it
        rides the same checkpoint tree (and target-matching) as the
        train state (reference AtorchTrainer persists sampler state)."""
        import json

        import numpy as np

        sd = self.train_data.state_dict()
        raw = json.dumps(sd).encode()
        if len(raw) > self._DATA_STATE_BYTES:
            logger.warning(
                "dataloader state too large to checkpoint (%d bytes)",
                len(raw),
            )
            return None
        buf = np.zeros(self._DATA_STATE_BYTES, np.uint8)
        buf[: len(raw)] = np.frombuffer(raw, np.uint8)
        return buf

    def _ckpt_tree(self):
        tree = {"train": self.state}
        if hasattr(self.train_data, "state_dict"):
            packed = self._pack_data_state()
            if packed is not None:
                tree["data"] = packed
        return tree

    def _release_state(self):
        """Free the live train state's device buffers and return its
        abstract twin (shape, dtype, sharding per leaf) — what a
        restore needs of a state it is about to replace."""
        import jax

        def abstract(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            )

        is_array = lambda x: isinstance(x, jax.Array)  # noqa: E731
        twin = jax.tree.map(
            lambda x: abstract(x) if is_array(x) else x, self.state
        )
        for leaf in jax.tree.leaves(self.state):
            if is_array(leaf):
                leaf.delete()
        self.state = self._accel.state = None
        return twin

    def maybe_resume(self) -> int:
        """Restore the newest checkpoint (shm preferred, then storage),
        including dataloader/sampler progress so a restarted job picks
        up mid-epoch instead of replaying from offset 0.
        Returns the restored step (0 = fresh)."""
        if self._engine is None:
            return 0
        tree = self._ckpt_tree()
        if self._engine.latest_step() >= 0:
            # restore INTO the state's place, not beside it: the
            # freshly initialised state is about to be replaced, and
            # holding it while the restored arrays land doubles the
            # resident bytes — a state over half the device memory (8 GB
            # of a 16 GB chip at two Llama-2-7B-width layers) could
            # never resume. The targets keep shapes and shardings only.
            tree["train"] = self._release_state()
        # fallback targets: a checkpoint written without the data leaf
        # (oversized loader state) and the pre-wrapper layout (bare
        # train state) must both keep restoring
        targets = [tree]
        if "data" in tree:
            targets.append({"train": tree["train"]})
        targets.append(tree["train"])
        restored = None
        first_err = None
        for tgt in targets:
            try:
                restored = self._engine.load(target=tgt)
            except ValueError as err:
                if first_err is None:
                    first_err = err
                continue
            if restored is not None:
                break
        if restored is None:
            if first_err is not None:
                if not os.environ.get("DLROVER_TPU_IGNORE_CKPT"):
                    raise ValueError(
                        f"existing checkpoint is incompatible with the "
                        f"current model/optimizer layout: {first_err}. "
                        f"Delete the checkpoint dir or set "
                        f"DLROVER_TPU_IGNORE_CKPT=1 to start fresh."
                    ) from first_err
                logger.warning(
                    "ignoring incompatible checkpoint "
                    "(DLROVER_TPU_IGNORE_CKPT set): %s", first_err,
                )
            if self.state is None:
                # released for a restore that found nothing usable
                self._adopt_accel(
                    list(self._accel.mesh.devices.flat), None
                )
            return 0
        tree, step = restored
        if isinstance(tree, dict) and "train" in tree:
            self.state = tree["train"]
            if "data" in tree and hasattr(
                self.train_data, "load_state_dict"
            ):
                import json

                import numpy as np

                raw = np.asarray(tree["data"]).tobytes().rstrip(b"\x00")
                if raw:
                    self.train_data.load_state_dict(
                        json.loads(raw.decode())
                    )
        else:
            self.state = tree
        self.global_step = int(step)
        self._restore_prestep_state()
        # a multi-GB restore can take minutes of wall time with zero
        # step progress; any active hang detector must restart its
        # stall clock or the fresh incarnation gets relaunched for
        # "hanging" right out of restore
        from dlrover_tpu.trainer.fault_tolerance import (
            notify_progress_reset,
        )

        notify_progress_reset("checkpoint-restore")
        logger.info("resumed from checkpoint step %s", step)
        return self.global_step

    # --------------------------------------------------------------- train

    def train(self):
        import jax

        args = self.args
        # post-mortem coverage for the worker: a SIGTERM (preemption,
        # agent stop) dumps the last spans/events + thread stacks
        flight.install()
        with tracing.start_leg("start.restore") as leg:
            resumed = self.maybe_resume()
            if leg is not None and self._engine is not None:
                stats = self._engine.last_restore_stats
                leg.annotate(**{
                    k: stats[k] for k in ("read_s", "verify_s", "h2d_s")
                    if k in stats
                })
        backend.begin_compile_leg(self)
        metrics = {}
        shm_saves = 0
        # a job resumed at/after max_steps is already done: don't train
        # an extra step or overwrite the final checkpoint
        stop = bool(args.max_steps) and self.global_step >= args.max_steps
        from dlrover_tpu.agent.monitor import write_runtime_metrics
        from dlrover_tpu.trainer.timer import Tag

        sampler = getattr(self.train_data, "sampler", None)
        # resume into the restored sampler epoch; don't set_epoch on the
        # resumed epoch itself (it would clear the mid-epoch offset)
        start_epoch = 0
        if resumed and sampler is not None:
            start_epoch = min(
                int(getattr(sampler, "epoch", 0)), args.num_epochs - 1
            )
        for epoch in range(start_epoch, args.num_epochs):
            if stop:
                break
            if sampler is not None and hasattr(sampler, "set_epoch"):
                if epoch != start_epoch:
                    sampler.set_epoch(epoch)
            # reshaped=True re-enters iter(self.train_data) WITHOUT
            # advancing the epoch: an in-process mesh reshape re-shards
            # the epoch remainder over the new world, and consumption
            # is recorded before each yield, so the fresh iterator
            # continues exactly after the already-trained batches
            reshaped = True
            while reshaped and not stop:
                reshaped = False
                data_iter = iter(self.train_data)
                while True:
                    # drain-step boundary: adopt a pending membership
                    # change (in-process mesh reshape) BETWEEN steps,
                    # then restart the epoch iterator over the
                    # re-sharded remainder
                    if self._maybe_reshape():
                        reshaped = True
                        break
                    # the host input pipeline's stall is a first-class
                    # diagnosis phase (data_wait vs compute vs ckpt
                    # blame): time the iterator pull into the shm ring
                    t_wait = time.time_ns()
                    try:
                        with tracing.annotation("train.data_wait"):
                            batch = next(data_iter)
                    except StopIteration:
                        break
                    wait_ns = time.time_ns() - t_wait
                    with tracing.annotation("train.publish"):
                        if self._timer is not None:
                            self._timer.record(
                                Tag.DATA_WAIT, t_wait, wait_ns
                            )
                        self._prof.on_step_start(self.global_step)
                    t_enter = time.time_ns()
                    with tracing.span(
                        "train.dispatch", step=self.global_step + 1
                    ):
                        rng = jax.random.fold_in(
                            jax.random.key(args.seed), self.global_step
                        )
                        if self.prestep is not None:
                            self.state, batch = self.prestep(
                                self.state, batch
                            )
                        self.state, metrics = self._accel.train_step(
                            self.state, batch, rng
                        )
                        self.global_step += 1
                    if self._tokens_per_step is None:
                        self._tokens_per_step = self._batch_tokens(batch)
                    self._pending.append((
                        self.global_step, metrics.get("loss", metrics),
                        t_enter, self._compiled_once,
                    ))
                    self._compiled_once = True
                    # a read-back waits for every step in flight, any
                    # other step for all but the newest STEP_LAG
                    readback = bool(args.log_steps) and \
                        self.global_step % args.log_steps == 0
                    self._close_steps(0 if readback else STEP_LAG)
                    with tracing.annotation("train.publish"):
                        self._emit_device_gauges()
                        write_runtime_metrics(self._completed_step)
                    if readback:
                        # two spans of one name: a profiler session that
                        # starts or stops at the log record (a benchmark's
                        # does) still holds the one it does not cut
                        with tracing.annotation("train.readback"):
                            loss = float(metrics.get("loss", float("nan")))
                            self._count_step(metrics)
                        with tracing.annotation("train.readback"):
                            logger.info(
                                "step %d epoch %d loss %.5f",
                                self.global_step, epoch, loss,
                            )
                            telemetry.flush()
                            self._maybe_adopt_cadence()
                            self._maybe_reprobe()
                    if (
                        self._engine is not None
                        and args.save_steps
                        and self.global_step % args.save_steps == 0
                    ):
                        shm_saves += 1
                        persist = (
                            shm_saves % max(args.save_storage_every, 1)
                            == 0
                        )
                        self.save_checkpoint(persist=persist)
                    if args.eval_steps and self.eval_data is not None \
                            and self.global_step % args.eval_steps == 0:
                        self.evaluate()
                    if args.max_steps and \
                            self.global_step >= args.max_steps:
                        stop = True
                        break
        self._close_steps()
        write_runtime_metrics(self._completed_step)
        if self._engine is not None:
            # The final checkpoint must not be lost to a cadence save's
            # persist still holding the shm lock: a silently skipped
            # save here would strand wait_for_persist on a step that
            # never arrives and drop the end-of-run state entirely.
            # Bounded retry until the in-flight persist drains —
            # EVENT-DRIVEN: each retry blocks on the saver's persist-
            # done queue (the lock holder is an in-flight persist, so
            # its completion is exactly the wakeup we need) with the
            # deadline as backstop, instead of quantizing end-of-run
            # latency to a fixed poll interval.
            deadline = time.time() + 120
            while not self.save_checkpoint(persist=True):
                remaining = deadline - time.time()
                if remaining <= 0:
                    logger.error(
                        "final checkpoint save at step %d kept getting "
                        "skipped; giving up", self.global_step,
                    )
                    break
                self._engine.wait_for_persist_progress(
                    min(remaining, 2.0)
                )
            else:
                t_wait = time.monotonic()
                self._engine.wait_for_persist(
                    self.global_step, timeout=300
                )
                # the ONLY persist the training loop blocks on — unlike
                # cadence persists it is real lost wall-clock
                telemetry.event(
                    "ckpt.persist.wait",
                    step=self.global_step,
                    dur=time.monotonic() - t_wait,
                )
        telemetry.flush()
        return self.state, metrics

    # ------------------------------------------------------ the step clock

    def _close_steps(self, keep: int = 0):
        """Wait, oldest first, for the steps in flight down to the
        newest ``keep`` and record each at its completion, one
        ``step.end`` a step. A step lasts from the later of the
        completion before it and the start of its own dispatch to its
        own completion, so durations tile the wall clock and a save or
        a read-back before a step is not booked to it. A completion is
        seen when the host looks: in a loop the host bounds (a slow
        input pipeline) a step reads the host's iteration, and
        ``Tag.DATA_WAIT`` says why. Every place the loop syncs with the
        device calls this first, with ``keep=0``."""
        import jax

        while len(self._pending) > keep:
            number, ready, t_enter, steady = self._pending.popleft()
            with tracing.annotation("train.step_wait"):
                jax.block_until_ready(ready)
            now = time.time_ns()
            start = max(self._last_done_ns, t_enter)
            self._last_done_ns = now
            with tracing.annotation("train.publish"):
                self._record_step(number, start, now - start, steady)

    def _record_step(self, number, start_ns, dur_ns, steady):
        """The one measurement of a step, handed to all its readers:
        the shm timer ring (agent exporter, straggler detector), the
        sampler's governor, the ``step.end`` event (goodput ledger,
        the master's median step and hang detector) and the gauges
        ``train.step.last_s`` / ``train.mfu``."""
        from dlrover_tpu.trainer.timer import Tag

        dur_s = dur_ns / 1e9
        self._completed_step = number
        if self._timer is not None:
            self._timer.record(
                Tag.STEP if steady else Tag.COMPILE, start_ns, dur_ns
            )
        # the sampler numbers a step as the loop does before it
        # dispatches it; a finished window parses off-thread. A
        # compiling step is no sample of its governor's step time.
        self._prof.on_step_end(number - 1, dur_s if steady else 0.0)
        if not steady:
            # the first step of an incarnation traces and compiles: its
            # wall time is no step-time/MFU sample, and one giant first
            # point would poison the SLO watchdog's rolling baselines
            telemetry.event("compile", step=number, dur=dur_s)
            backend.first_step_done()
            return
        telemetry.event("step.end", step=number, dur=dur_s)
        if dur_s <= 0:
            return
        telemetry.gauge_set("train.step.last_s", dur_s)
        if (
            self._tokens_per_step and self._peak_flops
            and self._flops_per_token > 0
        ):
            from dlrover_tpu.common import mfu

            telemetry.gauge_set(
                "train.mfu",
                mfu.mfu(
                    self._flops_per_token * self._tokens_per_step,
                    dur_s, self._peak_flops,
                ),
            )

    # ------------------------------------------- brain cadence adoption

    def _master(self):
        """The advisory master client, or None when there is no master
        (probed once)."""
        if self._advisory_client is None:
            try:
                from dlrover_tpu.agent.master_client import (
                    build_master_client,
                )

                self._advisory_client = build_master_client() or False
            except Exception:  # noqa: BLE001 - env without a master
                self._advisory_client = False
        return self._advisory_client or None

    def _maybe_reprobe(self):
        """Continuous hardware check, in band: a governed low-cadence
        re-probe (floor interval stretched until the probe costs under
        its overhead budget) at a step boundary of the process that
        holds the chip, feeding the master's fingerprint store —
        sustained degradation becomes a hw_degraded verdict and a
        drain, not a mystery slowdown. One report per host: local rank
        0 probes every local device."""
        from dlrover_tpu.common.constants import NodeEnv

        if (
            self._reprobe is None
            or not self._reprobe.due()
            or os.environ.get(NodeEnv.LOCAL_RANK, "0") != "0"
        ):
            return
        client = self._master()
        if client is None:
            return
        rank = int(os.environ.get(NodeEnv.NODE_RANK, "0"))
        report = self._reprobe.run(rank)
        try:
            client.report_probe(rank, report)
        except Exception:  # noqa: BLE001 - the health signal is
            # advisory; a dropped sample waits for the next window
            logger.warning("in-band probe report failed", exc_info=True)

    def _maybe_adopt_cadence(self):
        """Adopt the master brain's goodput-aware checkpoint cadence
        (Young/Daly-tuned ``save_steps``) from the run-config channel.
        Polled at log cadence, fail-fast and best-effort: no master
        (or an unreachable one) just keeps the configured value, and
        adoption never stalls the step loop."""
        if (
            not self.args.adopt_cadence
            or self._engine is None
            or not self.args.save_steps
        ):
            return
        client = self._master()
        if client is None:
            return
        try:
            configs = client.get_elastic_run_config(retries=1)
        except (ConnectionError, OSError):
            return
        except Exception:  # noqa: BLE001 - advisory channel
            return
        from dlrover_tpu.master.brain import CADENCE_CONFIG_KEY

        steps = int(configs.get(CADENCE_CONFIG_KEY, 0) or 0)
        if steps <= 0 or steps == self.args.save_steps:
            return
        was = self.args.save_steps
        self.args.save_steps = steps
        telemetry.event(
            "brain.cadence.adopted", save_steps=steps, was=was
        )
        telemetry.gauge_set("train.save_steps", steps)
        logger.info(
            "adopted brain checkpoint cadence: save_steps %d -> %d",
            was, steps,
        )

    # ------------------------------------------- live MFU / HBM gauges

    def _refresh_flops(self):
        """Model FLOPs per token and the mesh's peak FLOP/s, computed
        once per (re)shape — never in the step loop. Explicit
        ``model_flops_per_token`` wins (a caller that knows its
        attention term passes it); the fallback is the dense
        6 * params estimate."""
        from dlrover_tpu.common import mfu

        devices = self._accel.mesh.devices
        # None on the CPU: no peak, so no train.mfu gauge there
        self._peak_flops = mfu.peak_flops(devices.flat[0])
        if self._peak_flops:
            self._peak_flops *= devices.size
        if self.args.model_flops_per_token > 0:
            self._flops_per_token = float(
                self.args.model_flops_per_token
            )
            return
        try:
            import jax

            params = sum(
                x.size
                for x in jax.tree_util.tree_leaves(self.state.params)
            )
            self._flops_per_token = 6.0 * params
        except Exception:  # noqa: BLE001 - a non-standard state tree
            # just loses the MFU gauge, never the training loop
            self._flops_per_token = 0.0

    def _refresh_prof_context(self):
        """The op-cost baseline key (model fingerprint + mesh shape),
        computed once per (re)shape — a reshaped mesh gets its OWN
        baseline row, so a legitimate topology change never reads as
        an op-cost regression."""
        from dlrover_tpu.common import profiling

        try:
            self._prof.set_context(
                profiling.model_fingerprint(self.state.params),
                profiling.mesh_shape_key(self._accel.mesh),
            )
        except Exception:  # noqa: BLE001 - a non-standard state tree
            # only loses baseline keying, never the training loop
            self._prof.set_context("unfingerprinted", "devices=?")

    def _emit_device_gauges(self):
        """Per-device HBM gauges from ``device.memory_stats()`` where
        the backend provides them, plus host-arena occupancy. The
        device half is probed once — a backend without memory_stats
        costs one branch per step thereafter; the arena gauge is
        host-side and emits regardless."""
        if self._device_mem_ok is not False:
            try:
                import jax

                reported = False
                for i, dev in enumerate(jax.local_devices()):
                    mem = getattr(dev, "memory_stats", None)
                    m = mem() if callable(mem) else None
                    if not m:
                        continue
                    reported = True
                    telemetry.gauge_set(
                        "device.hbm.live_bytes",
                        m.get("bytes_in_use", 0), device=str(i),
                    )
                    if "peak_bytes_in_use" in m:
                        telemetry.gauge_set(
                            "device.hbm.peak_bytes",
                            m["peak_bytes_in_use"], device=str(i),
                        )
                    if "bytes_limit" in m:
                        telemetry.gauge_set(
                            "device.hbm.limit_bytes",
                            m["bytes_limit"], device=str(i),
                        )
                if self._device_mem_ok is None:
                    self._device_mem_ok = reported
            except Exception:  # noqa: BLE001 - gauges are garnish
                self._device_mem_ok = False
        try:
            from dlrover_tpu.common.arena import get_arena

            telemetry.gauge_set(
                "ckpt.arena.pooled_bytes",
                get_arena().stats()["pooled_bytes"],
            )
        except Exception:  # noqa: BLE001
            pass

    @staticmethod
    def _batch_tokens(batch) -> int:
        """Best-effort token count for the ``train.mfu`` gauge: the
        first 2-D integer leaf (token ids) wins; 0 when the batch has
        none (e.g. dense regression batches)."""
        try:
            import jax
            import numpy as np

            for leaf in jax.tree_util.tree_leaves(batch):
                shape = getattr(leaf, "shape", None)
                dtype = getattr(leaf, "dtype", None)
                if (
                    shape is not None
                    and len(shape) == 2
                    and dtype is not None
                    and np.issubdtype(np.dtype(dtype), np.integer)
                ):
                    return int(shape[0]) * int(shape[1])
        except Exception:  # noqa: BLE001 - the MFU gauge is garnish
            pass
        return 0

    # ------------------------------------------- in-process mesh reshape

    def _reshape_devices(self, req) -> list:
        """The device set of the post-reshape mesh. Deployment hook:
        ``reshape_devices_fn(req)`` decides (single-host tests emulate
        scale events with local-device subsets); default is the
        request's explicit ``device_count`` prefix, else every device
        this process can see."""
        import jax

        if self._reshape_devices_fn is not None:
            return list(self._reshape_devices_fn(req))
        if req.device_count:
            return list(jax.devices()[: req.device_count])
        return list(jax.devices())

    def _maybe_reshape(self) -> bool:
        """Adopt a pending membership change IN PROCESS: rebuild the
        mesh, reshard the live state device-to-device (checkpoint
        fallback only for shards whose owners died), re-shard the
        epoch remainder, and ack the agent. Returns True when a
        reshape happened (the caller restarts its epoch iterator).
        A failed reshape acks failure — the agent then falls back to
        the classic restart path."""
        if self._reshape_channel is None:
            return False
        with tracing.annotation("train.publish"):
            req = self._reshape_channel.poll(self._reshape_round)
        if req is None:
            return False
        self._close_steps()
        t0 = time.monotonic()
        ok, stats = False, {}
        # transaction snapshot: _apply_reshape mutates accel/state/
        # step/sampler in sequence, and a failure PAST any of those
        # mutations (a chaos error at the resume seam, a bad rank in
        # the data re-accounting) must not leave a half-adopted world
        # behind a failed ack — training would continue on the new
        # mesh with the OLD world's shard assignment until the agent's
        # restart lands, double-serving data. Old jax arrays are
        # immutable and not donated by the reshape, so restoring the
        # references restores the world.
        snap_accel, snap_state = self._accel, self.state
        snap_step, snap_compiled = self.global_step, self._compiled_once
        sampler = getattr(self.train_data, "sampler", None)
        snap_sampler = (
            (sampler.num_replicas, sampler.rank, sampler.state_dict())
            if sampler is not None and hasattr(sampler, "state_dict")
            else None
        )
        with tracing.span(
            "elastic.reshape", round=req.round, step=self.global_step
        ):
            try:
                stats = self._apply_reshape(req)
                ok = True
            except Exception as e:  # noqa: BLE001 - ANY failure here
                # must surface as a failed ack so the agent falls back
                # to the restart path instead of hanging on the ack
                logger.exception(
                    "in-process reshape for round %s failed; acking "
                    "failure (the agent restarts this worker)",
                    req.round,
                )
                stats = {"error": f"{type(e).__name__}: {e}"[:200]}
                self._accel, self.state = snap_accel, snap_state
                self.global_step = snap_step
                self._compiled_once = snap_compiled
                if snap_sampler is not None:
                    sampler.num_replicas, sampler.rank = snap_sampler[:2]
                    sampler.load_state_dict(snap_sampler[2])
                # known gap: a stateful prestep hook overwritten by the
                # in-process ROLLBACK's resume is not snapshotted here
                # (host tiers can be GBs); the restart this failed ack
                # triggers re-restores it from the step-matched sidecar
        dur = time.monotonic() - t0
        # ``step`` = the boundary the new mesh takes over at (post-
        # rollback step on the rollback path): the agent/harness uses
        # it to account the adoption against training progress
        self._reshape_channel.ack(
            req.round, ok, dur=dur, step=self.global_step, **stats
        )
        # consume the round even on failure: the agent's restart is
        # the retry path, and re-polling the same request at every
        # subsequent step boundary would re-run the reshape (and
        # re-fire its chaos seams) against a state that moved on
        self._reshape_round = req.round
        if not ok:
            return False
        telemetry.event(
            "elastic.reshape",
            dur=dur,
            round=req.round,
            step=self.global_step,
            shards_moved=stats.get("moved", 0),
            shards_pulled=stats.get("pulled", 0),
            rolled_back_to=stats.get("rolled_back_to", -1),
        )
        telemetry.observe("elastic.reshape.seconds", dur)
        telemetry.counter_inc("elastic.reshape.count")
        if stats.get("pulled"):
            telemetry.counter_inc(
                "elastic.reshape.shards_pulled", stats["pulled"]
            )
        telemetry.gauge_set("elastic.reshape.last_s", dur)
        telemetry.flush()
        logger.info(
            "adopted round %s in process in %.3fs (world=%s, moved=%s "
            "pulled=%s rolled_back_to=%s)",
            req.round, dur, req.world, stats.get("moved"),
            stats.get("pulled"), stats.get("rolled_back_to", -1),
        )
        return True

    def _apply_reshape(self, req) -> dict:
        import jax

        from dlrover_tpu.parallel.accelerate import (
            TrainState,
            compute_state_shardings,
            rules_for_mesh,
        )
        from dlrover_tpu.parallel.mesh import build_mesh
        from dlrover_tpu.parallel.reshaper import (
            reshape_pytree,
            survivors_cover,
        )
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            _tree_flatten_with_names,
        )

        chaos_point(
            "elastic.reshape", verb="drain", step=self.global_step,
            round=req.round,
        )
        devices = self._reshape_devices(req)
        strategy = self._accel.strategy
        mesh = build_mesh(strategy.mesh, devices=devices)
        rules = rules_for_mesh(strategy.rules, mesh)
        from jax.sharding import NamedSharding, PartitionSpec

        param_sh, opt_sh = compute_state_shardings(
            self.init_fn, self.optimizer, self.param_logical_axes,
            mesh, rules, seed=self.args.seed,
        )
        state_sh = TrainState(
            step=NamedSharding(mesh, PartitionSpec()),
            params=param_sh,
            opt_state=opt_sh,
        )
        # shards die with a DEAD host only; a drained host is alive at
        # the drain point, so everything it holds is still readable
        # device-to-device (the decision matrix in DESIGN.md)
        lost_devices: set = set()
        if any(
            reason == "dead" for reason in (req.departed or {}).values()
        ):
            old_ids = {d.id for d in self._accel.mesh.devices.flat}
            lost_devices = old_ids - {d.id for d in devices}
        # checkpoint-engine leaf names for the fallback loader: the
        # engine's own flatten of {"train": state} — the exact names
        # its saved shards carry
        names = _tree_flatten_with_names({"train": self.state})[0]
        if lost_devices:
            leaves = jax.tree_util.tree_leaves(self.state)
            if any(
                not survivors_cover(leaf, lost_devices)
                for leaf in leaves
            ):
                # CONSISTENCY GATE: a lost shard can only come from a
                # checkpoint, and a checkpoint older than the live
                # step would mix steps inside one state. Exactly at
                # the live step -> pull only the lost shards; older ->
                # roll the WHOLE state back in process (still no
                # process restart, no recompile of cached programs).
                ckpt_step = (
                    self._engine.latest_step()
                    if self._engine is not None else -1
                )
                if ckpt_step < 0:
                    raise ValueError(
                        "shards lost with a dead host and no "
                        "checkpoint exists — in-process reshape would "
                        "lose state"
                    )
                if ckpt_step != self.global_step:
                    return self._reshape_rollback(req, devices)
        chaos_point(
            "elastic.reshape", verb="reshard", step=self.global_step,
            round=req.round,
        )
        new_state, report = reshape_pytree(
            self.state,
            state_sh,
            lost_devices=lost_devices,
            fallback=self._pull_lost_shards,
            names=names,
        )
        self._adopt_accel(devices, new_state)
        chaos_point(
            "elastic.reshape", verb="resume", step=self.global_step,
            round=req.round,
        )
        self._reshape_data(req)
        return {
            "moved": report.moved,
            "pulled": report.pulled,
            "move_s": round(report.move_seconds, 6),
            "devices": len(devices),
        }

    def _pull_lost_shards(self, requests: dict) -> dict:
        """Fallback loader for leaves whose only shards died with a
        host: a TARGETED engine load keyed by checkpoint leaf names —
        shard-wise, so each new device shard reads only the byte
        ranges it needs from shm (preferred) or verified storage."""
        if self._engine is None:
            raise ValueError(
                "lost shards but flash checkpointing is disabled"
            )
        result = self._engine.load(target=dict(requests))
        if result is None:
            raise ValueError(
                f"lost shards {sorted(requests)[:3]} are not "
                f"restorable from any checkpoint"
            )
        tree, step = result
        if int(step) != self.global_step:
            raise ValueError(
                f"lost shards only restorable at step {step}, live "
                f"state is at step {self.global_step} — mixing steps "
                f"would corrupt the state"
            )
        return tree

    def _reshape_rollback(self, req, devices) -> dict:
        """Lost shards + no checkpoint at the live step: the whole
        state returns to the newest restorable checkpoint, IN PROCESS
        — fresh sharded init on the new mesh, then the standard
        targeted resume (train state + dataloader progress + prestep
        sidecar). Costs the replay since that step, but still no
        process teardown and no cold recompile."""
        import jax

        logger.warning(
            "reshape round %s: shards lost with a dead host and the "
            "newest checkpoint predates the live step — rolling back "
            "in process", req.round,
        )
        chaos_point(
            "elastic.reshape", verb="reshard", step=self.global_step,
            round=req.round,
        )
        self._adopt_accel(devices, None)
        self.global_step = 0
        resumed = self.maybe_resume()
        chaos_point(
            "elastic.reshape", verb="resume", step=self.global_step,
            round=req.round,
        )
        self._reshape_data(req)
        return {
            "moved": 0,
            "pulled": len(jax.tree_util.tree_leaves(self.state)),
            "rolled_back_to": resumed,
            "devices": len(devices),
        }

    def _count_step(self, metrics):
        """The loss function's own counts of the step just read back,
        on the host with the loss and with no wait of their own: only
        the names it declares as ``counters`` (any other aux is no
        count), each added to the counter of its name and all together
        left as one ``step.counts`` event, so that a reader can take
        the increments of a stretch of the run. The step hands back
        the mean over its micro-batches: times their number is the
        step's sum."""
        names = getattr(self.loss_fn, "counters", ())
        if not names:
            return
        accum = max(int(self._accel.strategy.grad_accum), 1)
        counts = {name: float(metrics[name]) * accum for name in names}
        for name, count in counts.items():
            telemetry.counter_inc(name, count)
        telemetry.event("step.counts", step=self.global_step, **counts)

    def _accelerate(self, strategy, **kwargs):
        """``auto_accelerate`` over this Trainer's model. A loss
        function may carry a form of itself that returns ``(loss,
        aux)`` as its ``with_aux`` attribute (aux: a flat dict of
        scalars, e.g. the tokens an expert layer routed). The step
        then trains on that form, and the aux comes back with the loss
        at each read-back (``_count_step``)."""
        with_aux = getattr(self.loss_fn, "with_aux", None)
        return auto_accelerate(
            with_aux or self.loss_fn, self.init_fn, self.optimizer,
            self.param_logical_axes, strategy=strategy,
            seed=self.args.seed, has_aux=with_aux is not None, **kwargs,
        )

    def _adopt_accel(self, devices, state):
        """Rebuild mesh + shardings + jitted step for the new device
        set. ``state=None`` re-initializes (rollback path); otherwise
        the resharded live state is adopted as-is. The first step on
        the new mesh retraces — against the persistent XLA compilation
        cache that is a cache replay, and it is charged to the
        ``compile`` goodput bucket either way."""
        self._accel = self._accelerate(
            self._accel.strategy, devices=devices, reuse_state=state
        )
        self.state = self._accel.state if state is None else state
        self._compiled_once = False
        self._tokens_per_step = None
        # model FLOPs are a per-(re)shape constant, not a per-step one
        self._refresh_flops()
        # ...and so is the op-cost baseline key (new mesh shape)
        self._refresh_prof_context()

    def _reshape_data(self, req):
        """Exactly-once dataset re-accounting: re-shard the epoch
        remainder over the new world. Loaders without a ``reshape``
        hook (plain lists, master-served sharding clients — the
        latter's exactly-once story lives in the master's dataset
        manager) are left alone."""
        if not hasattr(self.train_data, "reshape"):
            return
        from dlrover_tpu.common.constants import NodeEnv

        local_rank = int(
            os.environ.get(NodeEnv.LOCAL_RANK, "0") or 0
        )
        self.train_data.reshape(
            max(int(req.total), 1), req.rank_offset + local_rank
        )

    # --------------------------------------------------------- checkpoints

    def save_checkpoint(self, persist: bool = False):
        if self._engine is None:
            return False
        self._close_steps()
        tree = self._ckpt_tree()
        # PENDING sidecar before the engine commit, promoted to latest
        # only after the save succeeds: a crash on either side of the
        # engine's two-phase shm publish (e.g. a worker killed right
        # after the save — the canonical chaos scenario) leaves the
        # restored step matching either the pending sidecar (crash
        # after publish, before promote) or the promoted latest one
        # (crash before publish, and any number of SKIPPED saves),
        # so resume never hard-fails on a step-mismatched pair.
        self._write_prestep_pending()
        if persist:
            ok = self._engine.save_to_storage(self.global_step, tree)
        else:
            ok = self._engine.save_to_memory(self.global_step, tree)
        if ok:
            self._promote_prestep_pending(persist)
        return ok

    # three sidecars: the latest SUCCESSFUL (memory-cadence) save, the
    # pre-commit PENDING one (crash bracket, see save_checkpoint), and
    # the latest PERSISTED save — a restore can land on any of those
    # steps (shm vs storage vs interrupted commit), and the mapper must
    # pair with the exact table step it was saved with; a mismatched
    # pair silently scrambles embeddings
    _PRESTEP_FILES = (
        "prestep_state.npy",
        "prestep_state_pending.npy",
        "prestep_state_persist.npy",
    )

    def _prestep_stateful(self) -> bool:
        """Save and restore must gate on the SAME capability check — a
        hook with only one of the pair would otherwise write sidecars
        it can't load, or demand sidecars that were never written."""
        return hasattr(self.prestep, "state_dict") and hasattr(
            self.prestep, "load_state_dict"
        )

    def _write_prestep_pending(self):
        """Sidecar for stateful prestep hooks (e.g. a tiered embedding's
        id -> slot mapper + host rows): variable-sized host arrays can't
        ride the engine's shape-matched tree, so they are written next
        to the checkpoint at every save, tagged with the step so resume
        can refuse a mismatched pair. Written to the PENDING slot before
        the engine commit (promoted on success): the latest sidecar only
        ever advances in lockstep with a save that actually landed.
        Runs at memory-save cadence because shm is the preferred restore
        source — with a very large host tier, raise ``save_steps`` to
        bound the sidecar I/O."""
        if not self._prestep_stateful():
            return
        # the prestep state cannot change while global_step stands
        # still, so retries of the same step (the final-save retry
        # loop) must not re-serialize a possibly multi-GB host tier
        # every 200 ms
        if self._prestep_sidecar_step == self.global_step:
            return
        import numpy as np

        os.makedirs(self.args.output_dir, exist_ok=True)
        payload = np.array(
            {"step": self.global_step,
             "state": self.prestep.state_dict()},
            dtype=object,
        )
        pending = os.path.join(
            self.args.output_dir, self._PRESTEP_FILES[1]
        )
        # prestep sidecar seam (dlint DL003): PR 2's pending-then-
        # promote scheme exists exactly for kills around this write —
        # make the write itself schedulable too
        chaos_point("ckpt.prestep", step=self.global_step)
        tmp = pending + ".tmp"
        with open(tmp, "wb") as f:  # np.save(str) appends .npy
            np.save(f, payload, allow_pickle=True)
        os.replace(tmp, pending)
        self._prestep_sidecar_step = self.global_step

    def _promote_prestep_pending(self, persist: bool):
        """The save landed: the pending sidecar becomes the latest (and
        the persist snapshot when the save persisted). Rename + hard
        link — no second serialization of the host tier. The pending
        file may already have been promoted by an earlier success at
        the same step (skipped rewrite); the persist link then snapshots
        the promoted latest."""
        if not self._prestep_stateful():
            return
        pending = os.path.join(
            self.args.output_dir, self._PRESTEP_FILES[1]
        )
        latest = os.path.join(
            self.args.output_dir, self._PRESTEP_FILES[0]
        )
        if os.path.exists(pending):
            os.replace(pending, latest)
        if not os.path.exists(latest):
            return
        if persist:
            for dst in (
                os.path.join(
                    self.args.output_dir, self._PRESTEP_FILES[2]
                ),
                # one snapshot PER persisted step: the engine's
                # verified-restore may fall back past the newest step
                # (torn/bit-flipped shards), and the matching mapper for
                # that older step must still exist or the fallback dead-
                # ends in a step-mismatch refusal
                os.path.join(
                    self.args.output_dir,
                    self._PRESTEP_STEP_PREFIX
                    + f"{self.global_step}.npy",
                ),
            ):
                tmp = dst + ".tmp"
                try:
                    os.link(latest, tmp)
                except OSError:
                    import shutil

                    shutil.copyfile(latest, tmp)
                os.replace(tmp, dst)
            self._prune_prestep_steps()

    _PRESTEP_STEP_PREFIX = "prestep_state_step"
    _PRESTEP_KEEP_STEPS = 4

    def _prestep_keep_steps(self) -> int:
        """Per-step sidecar retention follows the checkpoint retention
        policy when one is configured (a verified fallback can only
        land on a retained step dir, and its sidecar must still
        exist); otherwise a fixed recent window."""
        try:
            keep = int(
                os.environ.get("DLROVER_TPU_MAX_CKPTS_TO_KEEP", "0")
            )
        except ValueError:
            keep = 0
        return max(keep, self._PRESTEP_KEEP_STEPS)

    def _prestep_step_files(self) -> list[str]:
        """Per-persisted-step sidecar snapshots, newest step first."""
        import glob

        def step_of(p):
            stem = os.path.basename(p)[
                len(self._PRESTEP_STEP_PREFIX):-len(".npy")
            ]
            try:
                return int(stem)
            except ValueError:
                return -1

        return sorted(
            glob.glob(os.path.join(
                self.args.output_dir,
                self._PRESTEP_STEP_PREFIX + "*.npy",
            )),
            key=step_of,
            reverse=True,
        )

    def _prune_prestep_steps(self):
        for path in self._prestep_step_files()[
            self._prestep_keep_steps():
        ]:
            try:
                os.remove(path)
            except OSError:
                pass

    def _restore_prestep_state(self):
        """Load the sidecar whose step matches the restored checkpoint
        exactly. No match = the mapper would pair with a table from a
        different step (silently wrong embeddings), so refuse unless
        DLROVER_TPU_IGNORE_CKPT opts into starting from empty state."""
        if not self._prestep_stateful():
            return
        import numpy as np

        seen_steps = []
        candidates = [
            os.path.join(self.args.output_dir, name)
            for name in self._PRESTEP_FILES
        ] + self._prestep_step_files()
        for path in candidates:
            if not os.path.exists(path):
                continue
            try:
                payload = np.load(path, allow_pickle=True).item()
                step = int(payload["step"])
            except Exception as e:  # noqa: BLE001 - torn/bit-rotted
                # sidecar: skip it and keep scanning — another snapshot
                # (persist copy, per-step file) may match, and a crash
                # loop over one rotten file would be strictly worse
                logger.warning(
                    "unreadable prestep sidecar %s (%s); skipping", path, e
                )
                continue
            if step == self.global_step:
                self.prestep.load_state_dict(payload["state"])
                return
            seen_steps.append(step)
        seen_steps = sorted(set(seen_steps))
        if os.environ.get("DLROVER_TPU_IGNORE_CKPT"):
            logger.warning(
                "no prestep sidecar matches restored step %s (found "
                "steps %s); starting the prestep hook from empty state "
                "(DLROVER_TPU_IGNORE_CKPT set)",
                self.global_step, seen_steps,
            )
            return
        raise ValueError(
            f"checkpoint restored step {self.global_step} but the "
            f"prestep sidecar(s) in {self.args.output_dir} hold steps "
            f"{seen_steps}: loading a mismatched id->slot map would "
            f"silently corrupt the restored embedding table. Delete "
            f"the checkpoint dir or set DLROVER_TPU_IGNORE_CKPT=1 to "
            f"start the prestep hook from empty state."
        )

    # ---------------------------------------------------------------- eval

    def evaluate(self) -> float:
        import jax
        import jax.numpy as jnp

        if self.eval_data is None:
            return float("nan")
        self._close_steps()
        eval_step = getattr(self, "_eval_step", None)
        if eval_step is None:
            def _eval(params, batch):
                return self.eval_fn(params, batch, jax.random.key(0))

            eval_step = jax.jit(_eval)
            self._eval_step = eval_step
        losses = []
        for batch in self.eval_data:
            # eval batches need the same host-side preparation as train
            # ones (raw ids -> device-resident slots); the table update
            # it threads back only changes row PLACEMENT, not values.
            # count=False where supported: eval traffic must not
            # inflate the frequency stats that drive demotion/eviction
            if self.prestep is not None:
                if self._prestep_accepts_count:
                    self.state, batch = self.prestep(
                        self.state, batch, count=False
                    )
                else:
                    self.state, batch = self.prestep(self.state, batch)
            losses.append(eval_step(self.state.params, batch))
        if self.prestep is not None:
            # eval's prepare_batch mutates row PLACEMENT at an
            # unchanged global_step: the same-step sidecar-skip cache
            # must not let a later save pair the post-eval table with a
            # pre-eval mapper snapshot
            self._prestep_sidecar_step = None
        loss = float(jnp.mean(jnp.stack(losses))) if losses else float(
            "nan"
        )
        logger.info("eval at step %d: loss %.5f", self.global_step, loss)
        return loss

    def close(self):
        self._pending.clear()
        self._prof.close()
        if self._engine is not None:
            self._engine.close()
