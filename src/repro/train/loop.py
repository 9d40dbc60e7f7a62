"""The one training loop driving every runtime (see DESIGN.md §2).

Owns the runtime-agnostic half of training:

  * the step loop with warmup/eval cadence,
  * wall-clock + tokens/s throughput accounting,
  * comm-bytes accounting from :mod:`repro.comm.bytes_model` (per outer
    sync: payload bytes, blocking bytes, messages),
  * a JSONL telemetry event stream (``run_start`` / ``step`` / ``outer`` /
    ``stream_sync`` / ``eval`` / ``ckpt`` / ``run_end`` events, one JSON
    object per line; ``stream_sync`` records each staggered stream exchange —
    stream id, round offset, bytes, blocked vs overlapped),
  * periodic checkpointing with FULL resume: program state (θ/φ/δ/opt/step
    counters via ``TrainProgram.state_pytree``) plus the loop's own PRNG keys
    and step cursor; the data loader is fast-forwarded deterministically
    (``make_loader(start_step)``), so a resumed run reproduces the
    uninterrupted loss trajectory exactly (tested).

Per-step PRNG keys are ``fold_in(base, t)`` rather than a split chain, so the
stream at step t is independent of eval cadence and survives resume without
replaying t splits.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Iterator

import numpy as np

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt_lib
from repro import obs
from repro.train.program import TrainProgram

__all__ = ["LoopConfig", "TrainLoop", "make_loop"]


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    """Runtime-agnostic knobs of the training loop."""

    steps: int
    eval_every: int = 0         # 0: never evaluate mid-run
    seed: int = 0               # base of the per-step PRNG fold-in streams
    ckpt_dir: str | None = None
    ckpt_every: int = 0         # 0: only the final save (when ckpt_dir set)
    ckpt_keep: int = 3          # retained periodic checkpoints
    resume: bool = False        # restore from latest ckpt under ckpt_dir
    log_jsonl: str | None = None  # telemetry stream path (appended on resume)
    log: bool = False           # human-readable progress prints
    run_name: str = "train"     # tag in telemetry events


class TrainLoop:
    """Drive a :class:`~repro.train.program.TrainProgram` end to end.

    ``make_loader(start_step)`` must return the deterministic stacked-batch
    stream beginning at ``start_step`` (see :func:`repro.data.shard_iterator`);
    ``eval_set`` is a fixed list of stacked batches (may be empty).
    """

    def __init__(
        self,
        program: TrainProgram,
        make_loader: Callable[[int], Iterator[dict]],
        cfg: LoopConfig,
        *,
        eval_set: list[dict] | None = None,
    ):
        self.program = program
        self.make_loader = make_loader
        self.cfg = cfg
        self.eval_set = eval_set or []
        self._jsonl = None

    # -- telemetry -----------------------------------------------------------

    def _emit(self, event: str, **fields) -> None:
        if self._jsonl is None:
            return
        rec = {"event": event, "run": self.cfg.run_name, **fields}
        with obs.span("train.telemetry"):
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()

    # -- checkpointing -------------------------------------------------------

    def _save(self, step: int, state, rngs: dict) -> str:
        with obs.span("train.checkpoint", step=step):
            tree = {
                "program": self.program.state_pytree(state),
                "loop": {"step": np.int64(step), **rngs},
            }
            path = ckpt_lib.save(
                self.cfg.ckpt_dir, step, tree, keep=self.cfg.ckpt_keep
            )
        self._emit("ckpt", step=step, path=path)
        return path

    def _try_resume(self, state):
        """Returns (state, start_step, rngs) — restored when possible."""
        cfg = self.cfg
        base = {
            "train_key": jax.random.PRNGKey(cfg.seed + 1),
            "eval_key": jax.random.PRNGKey(cfg.seed + 777),
        }
        if not (cfg.resume and cfg.ckpt_dir):
            return state, 0, base
        step = ckpt_lib.latest_step(cfg.ckpt_dir)
        if step is None:
            return state, 0, base
        tree = ckpt_lib.restore(cfg.ckpt_dir, step)
        state = self.program.load_state_pytree(state, tree["program"])
        rngs = {
            "train_key": jnp.asarray(tree["loop"]["train_key"]),
            "eval_key": jnp.asarray(tree["loop"]["eval_key"]),
        }
        return state, int(tree["loop"]["step"]), rngs

    # -- the loop ------------------------------------------------------------

    def run(self) -> dict[str, Any]:
        cfg = self.cfg
        if cfg.log_jsonl:
            self._jsonl = open(cfg.log_jsonl, "a")

        # init against an example batch from a THROWAWAY iterator so training
        # itself consumes the exact stream from start_step on
        state = self.program.init_state(next(self.make_loader(0)))
        state, start_step, rngs = self._try_resume(state)
        loader = self.make_loader(start_step)

        cost = self.program.comm_cost()
        self._emit(
            "run_start",
            program=type(self.program).__name__,
            replicas=self.program.replicas,
            steps=cfg.steps,
            start_step=start_step,
            resumed=start_step > 0,
            comm=cost.as_dict() if cost else None,
        )

        losses: list[float] = []
        evals: list[tuple[int, float]] = []
        weight_stds: list[tuple[int, float]] = []
        outer_syncs = 0
        comm_bytes = 0
        blocking_bytes = 0
        total_tokens = 0
        recompiles = 0
        max_staleness = 0
        blocked_syncs = 0
        # elastic programs expose an epoch-stamped Membership; emit a
        # telemetry event whenever the view changes (drop / rejoin)
        last_epoch = getattr(self.program, "membership_epoch", None)
        t0 = time.perf_counter()

        for t in range(start_step, cfg.steps):
            batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
            step_t0 = time.perf_counter()
            state, metrics = self.program.inner_step(
                state, batch, jax.random.fold_in(rngs["train_key"], t)
            )
            with obs.span("train.loss_fetch"):
                loss = float(jnp.mean(metrics["loss"]))
            losses.append(loss)
            total_tokens += int(np.prod(batch["tokens"].shape))
            state, synced = self.program.maybe_outer_step(state)
            # elastic shard_map programs recompile at membership-view
            # boundaries (OuterProgramPool): surface every compile as its own
            # telemetry event so churn-induced stalls are visible in
            # BENCH_engine-style runs (epoch, pool slot, build + first-call
            # wall-clock, pool size)
            drain = getattr(self.program, "drain_recompile_events", None)
            if drain is not None:
                for ev in drain():
                    recompiles += 1
                    self._emit("recompile", step=t + 1, **ev)
            # async merged-tick rounds (SimCluster per-replica clocks): one
            # event per sync carrying the due set, per-replica staleness τ and
            # the blocked-participant count; the synchronous baseline emits
            # the same shape (τ≡0) so blocked/idle comparisons line up
            adrain = getattr(self.program, "drain_async_events", None)
            if adrain is not None:
                for ev in adrain():
                    max_staleness = max(max_staleness, int(ev.get("max_staleness", 0)))
                    blocked_syncs += int(ev.get("blocked", 0))
                    self._emit("outer_async", step=t + 1, **ev)
            epoch = getattr(self.program, "membership_epoch", None)
            if epoch != last_epoch:
                last_epoch = epoch
                mem = self.program.membership
                self._emit(
                    "membership", step=t + 1, epoch=epoch,
                    num_active=mem.num_active, active=list(mem.active_ids),
                )
            dt = time.perf_counter() - step_t0
            self._emit(
                "step", step=t + 1, loss=loss, dt_s=round(dt, 6),
                tokens_per_s=round(total_tokens / max(time.perf_counter() - t0, 1e-9), 1),
            )
            if synced:
                outer_syncs += 1
                # streaming programs report the ACTUAL per-stream schedule
                # (which stream synced, whether its prefetch was consumed or
                # it fell back to blocking); byte accounting then follows the
                # events instead of the static whole-payload cost
                sdrain = getattr(self.program, "drain_stream_events", None)
                sevents = sdrain() if sdrain is not None else []
                if sevents:
                    payload = sum(ev["payload_bytes"] for ev in sevents)
                    blocking = sum(ev["blocking_bytes"] for ev in sevents)
                    comm_bytes += payload
                    blocking_bytes += blocking
                    for ev in sevents:
                        self._emit("stream_sync", step=t + 1, **ev)
                    self._emit(
                        "outer", step=t + 1, sync_index=outer_syncs,
                        payload_bytes=payload, blocking_bytes=blocking,
                    )
                else:
                    if cost is not None:
                        comm_bytes += cost.payload_bytes
                        blocking_bytes += cost.blocking_bytes
                    self._emit(
                        "outer", step=t + 1, sync_index=outer_syncs,
                        payload_bytes=cost.payload_bytes if cost else 0,
                        blocking_bytes=cost.blocking_bytes if cost else 0,
                    )
            if cfg.eval_every and (t + 1) % cfg.eval_every == 0 and self.eval_set:
                with obs.span("train.eval", step=t + 1):
                    ev = float(np.mean([
                        self.program.eval_step(
                            state, b, jax.random.fold_in(rngs["eval_key"], t)
                        )
                        for b in self.eval_set
                    ]))
                    wstd = float(self.program.weight_std(state))
                evals.append((t + 1, ev))
                weight_stds.append((t + 1, wstd))
                self._emit("eval", step=t + 1, eval_loss=ev, weight_std=wstd)
                if cfg.log:
                    print(
                        f"step {t+1}: train={loss:.4f} eval={ev:.4f} "
                        f"wstd={wstd:.6f} ({time.perf_counter()-t0:.0f}s)", flush=True
                    )
            if cfg.ckpt_dir and cfg.ckpt_every and (t + 1) % cfg.ckpt_every == 0:
                self._save(t + 1, state, rngs)

        wall = time.perf_counter() - t0
        already_saved = (
            cfg.ckpt_every and cfg.steps % cfg.ckpt_every == 0
        )
        if cfg.ckpt_dir and cfg.steps > start_step and not already_saved:
            self._save(cfg.steps, state, rngs)
        final_std = float(self.program.weight_std(state))
        tokens_per_s = total_tokens / max(wall, 1e-9)
        summary = {
            "steps_run": cfg.steps - start_step,
            "start_step": start_step,
            "wall_s": wall,
            "tokens_per_s": tokens_per_s,
            "outer_syncs": outer_syncs,
            "comm_bytes": comm_bytes,
            "blocking_bytes": blocking_bytes,
            "blocking_fraction": (
                blocking_bytes / comm_bytes if comm_bytes else 0.0
            ),
            "final_weight_std": final_std,
            "membership_epoch": last_epoch,
            "recompiles": recompiles,
            "stream_count": getattr(cost, "stream_count", 1) if cost else 1,
        }
        if getattr(self.program, "drain_async_events", None) is not None:
            summary["max_staleness"] = max_staleness
            summary["blocked_syncs"] = blocked_syncs
        stats_fn = getattr(self.program, "pool_stats", None)
        pool_stats = stats_fn() if stats_fn is not None else None
        if pool_stats is not None:
            summary["pool"] = pool_stats
        self._emit("run_end", **summary)
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
        return {
            "losses": losses,
            "evals": evals,
            "weight_stds": weight_stds,
            "state": state,
            "comm": cost.as_dict() if cost else None,
            **summary,
        }


def make_loop(
    program: TrainProgram, loader_cfg, cfg: LoopConfig, *, n_eval: int = 2
) -> TrainLoop:
    """Standard loop assembly shared by the launcher CLIs: train stream from
    ``loader_cfg`` (a :class:`repro.data.LoaderConfig`, fast-forwardable via
    ``start_step``), eval stream from the ``seed + 777`` convention."""
    from repro.data import eval_batches, shard_iterator

    eval_cfg = dataclasses.replace(loader_cfg, seed=loader_cfg.seed + 777)
    return TrainLoop(
        program,
        lambda start: shard_iterator(loader_cfg, start_step=start),
        cfg,
        eval_set=eval_batches(eval_cfg, n_eval) if cfg.eval_every else [],
    )
