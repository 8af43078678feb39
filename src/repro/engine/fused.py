"""FusedEngine — whole round chunks device-resident (DESIGN.md §8.6).

The eager round loop — even fully compiled — pays per-round host costs:
poll losses to numpy, run the strategy, re-upload the mask, dispatch
three separate jits, and copy the params pytree on every aggregation.
``FLConfig.fuse_rounds > 0`` removes all of it for the compiled backend:
chunks of up to ``fuse_rounds`` rounds run as **one** jitted
``lax.scan`` whose carry is ``(params, prng_key)`` and whose per-step
body is the canonical round —

    poll_losses → select_mask_traced → cohort gather+train → fedavg

with selection *fully traced*: the strategy's ``select_mask_traced``
hook (``supports_traced_selection``) expresses the per-round decision in
jax ops, drawing any randomness from the JAX PRNG stream, so no host
synchronization happens between rounds.  The carry arguments are
**donated** (``donate_argnums``), so the params pytree is updated in
place across the chunk instead of being copied once per round.

Chunk boundaries respect the absolute ``eval_every`` cadence: a chunk
always ends at an evaluation round (and at the configured terminal
round, and at any checkpoint save point — DESIGN.md §12), so evaluation
and saves see exactly the params the eager loop would have committed —
``rounds()`` still streams one frozen ``RoundResult`` per round by
unpacking the scanned per-round outputs (masks + cohort losses), and
chunked ``rounds()`` calls stay equivalent to one contiguous call.  Each distinct chunk length compiles once and is
cached; with an aligned ``fuse_rounds``/``eval_every`` there are at most
three lengths in play (the round-0 chunk, the steady-state chunk, the
tail).

PRNG discipline is unchanged (§8.3): the carry key splits 3-ways per
scan step exactly like the eager loop, and per-client training keys are
``fold_in``-derived by client index — so for strategies whose selection
is deterministic given losses (``fedlecc``, ``lossonly``, ``haccs``)
a fused run reproduces the eager compiled run round for round.
``clusterrandom`` draws its random scores from a key folded off the
poll key (a stream the eager path never consumes), making fused runs
self-consistent but intentionally not host-lockstep.

Consumption contract: state (params, round counter, comm ledger, PRNG
carry) commits at *chunk* granularity — abandoning the ``rounds()``
iterator mid-chunk leaves the engine at the chunk boundary, not at the
last yielded round.  Donation has teeth: every chunk *consumes* the
buffers behind ``engine.params`` and the PRNG carry, so (1) a reference
to ``engine.params`` taken before a ``rounds()`` call raises ``Array
has been deleted`` on first access afterwards — snapshot with
``jax.device_get(engine.params)`` (or ``jax.tree.map(jnp.copy, ...)``)
instead of aliasing; (2) an exception that lands between a chunk
dispatch and its commit (e.g. ``KeyboardInterrupt``) can leave the
engine's params already donated — treat an interrupted fused engine as
dead and rebuild it.  The eager backends share neither hazard.
"""

from __future__ import annotations

from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.selection import cohort_indices, selection_weights
from repro.engine.base import RoundResult, _mean_loss
from repro.engine.compiled import CompiledEngine
from repro.engine.config import fused_aggregator_error, fused_strategy_error
from repro.engine.trace import scope, span, step, to_host

__all__ = ["FusedEngine"]


class FusedEngine(CompiledEngine):
    """CompiledEngine semantics with scan-fused, donated round chunks."""

    backend = "compiled"  # fused is an execution mode of the compiled backend

    def __init__(self, cfg, train, test, n_classes: int, partition_labels=None):
        super().__init__(cfg, train, test, n_classes,
                         partition_labels=partition_labels,
                         cohort_gather=True)
        # defense in depth behind the up-front FLConfig validation
        if not getattr(self.strategy, "supports_traced_selection", False):
            raise ValueError(fused_strategy_error(cfg.strategy))
        if cfg.aggregator != "fedavg":
            raise ValueError(fused_aggregator_error(cfg.aggregator))
        self._chunk_cache: dict[int, Callable] = {}
        self._build_fused_round_body()

    # ------------------------------------------------------------------
    def _build_fused_round_body(self) -> None:
        from repro.federated.aggregation import fedavg

        cfg = self.cfg
        K = cfg.n_clients
        m = min(self.m_eff, K)
        strategy = self.strategy
        needs_losses = strategy.needs_losses
        sizes = self._sizes_j
        xs, ys, dmask = self.xs, self.ys, self.mask
        poll = self._poll_losses
        cohort_train = self._cohort_train_raw
        systems = self._systems is not None
        faults = self._faults is not None
        fruntime = self._faults
        defended = faults and fruntime.defended
        compress = cfg.compress_bits
        if compress:
            from functools import partial

            from repro.federated.compression import compressed_fedavg

            compressed = partial(compressed_fedavg, bits=compress)

        def _round_body(carry, inputs):
            params, key = carry
            # identical key discipline to Engine.rounds(): one 3-way
            # split per round off the persisted carry
            key, k_poll, k_train = jax.random.split(key, 3)
            if needs_losses:
                with scope("poll"):
                    losses = poll(params, xs, ys, dmask, k_poll)
            else:
                losses = jnp.zeros((K,), jnp.float32)
            # the availability / deadline traces (DESIGN.md §10) and the
            # fault-axis admission + injection decisions (§14) are all
            # exogenous host-precomputed scan inputs; the -inf gate below
            # is the same one the eager loop applies (_gated_losses)
            gate = None
            if systems:
                gate = inputs["avail"]
            if faults:
                gate = (
                    inputs["admit"] if gate is None
                    else gate & inputs["admit"]
                )
            if gate is not None:
                losses = jnp.where(gate, losses, -jnp.inf)
            with scope("select"):
                # selection randomness rides a stream the eager path
                # never consumes (fold tag K ≥ any client index), so
                # deterministic strategies stay bit-compatible with the
                # eager loop
                mask = strategy.select_mask_traced(
                    losses, jax.random.fold_in(k_poll, K)
                )
                idx = cohort_indices(mask, m)
            # survivors: offline-at-dispatch and past-deadline clients
            # keep their static cohort slot but aggregate at weight zero
            final = mask
            if systems:
                final = final & inputs["avail"] & inputs["arrived"]
            if faults:
                final = final & inputs["admit"]
            arrivals = final  # pre-flag: the updates reaching the server
            with scope("train"):
                stacked, sel_losses = cohort_train(params, idx, k_train)
                if faults:
                    # faults are upload properties: only rows whose
                    # upload reaches the server are injected (a
                    # zero-weight NaN row would still poison the
                    # mask-gated sum)
                    arrived_rows = jnp.take(arrivals, idx)
                    kind_rows = jnp.where(
                        arrived_rows, jnp.take(inputs["fkind"], idx), -1
                    )
                    u_rows = jnp.take(inputs["fu"], idx)
                    stacked = fruntime.apply_traced(
                        stacked, params, kind_rows, u_rows
                    )
                    if defended:
                        stacked, flagged_rows, _ = fruntime.validate_traced(
                            stacked, params, arrived_rows
                        )
                        # quarantine takes effect at weight exactly zero
                        flag_full = (
                            jnp.zeros((K,), bool).at[idx].max(flagged_rows)
                        )
                        final = final & ~flag_full
            with scope("aggregate"):
                w = jnp.take(selection_weights(final, sizes), idx)
                if compress:
                    new_params, _ = compressed(
                        stacked, params, w, self._quant_key(k_train, K)
                    )
                else:
                    new_params = fedavg(stacked, w)
                if systems or faults:
                    # nobody uploaded (or everyone was flagged) → the
                    # global model stands still (the all-zero weight
                    # vector would otherwise zero the params)
                    any_up = final.any()
                    new_params = jax.tree.map(
                        lambda n, o: jnp.where(any_up, n, o), new_params, params
                    )
            outs = (mask, final, sel_losses)
            if faults:
                outs = outs + (arrivals,)
            return (new_params, key), outs

        self._round_body = _round_body

    def _chunk_step(self, length: int) -> Callable:
        """The jitted chunk runner for one static chunk length — compiled
        once per distinct length, carry buffers donated.  With a systems
        config the chunk additionally takes the (length, K) availability
        and deadline-arrival traces as (undonated) scan inputs — their
        shapes depend only on the chunk length, so the cache key is
        unchanged and nothing retraces."""
        fn = self._chunk_cache.get(length)
        if fn is None:
            body = self._round_body
            if self._systems is not None or self._faults is not None:
                def run(params, key, inputs):
                    (params, key), out = jax.lax.scan(
                        body, (params, key), inputs, length=length
                    )
                    return params, key, *out
            else:
                def run(params, key):
                    (params, key), out = jax.lax.scan(
                        body, (params, key), None, length=length
                    )
                    return params, key, *out

            fn = jax.jit(run, donate_argnums=(0, 1))
            self._chunk_cache[length] = fn
        return fn

    def _chunk_len(self, rnd: int, end: int) -> int:
        """Rounds to fuse starting at absolute round ``rnd``: capped by
        ``fuse_rounds`` and clipped so the chunk ends exactly at the next
        ``eval_every``-cadence round, the configured terminal round, the
        call's final round, or the next checkpoint save point — so
        evaluation always sees chunk-boundary params, and a save policy
        with a round trigger always fires on committed chunk-boundary
        state.  Apart from the ``end`` clamp, the boundary is a pure
        function of the absolute round index, so a run resumed from a
        save point replays the identical chunk pattern (DESIGN.md §12)."""
        cfg = self.cfg
        ev = cfg.eval_every
        next_eval = rnd if rnd % ev == 0 else (rnd // ev + 1) * ev
        boundary = min(next_eval, end - 1)
        if rnd <= cfg.rounds - 1:
            boundary = min(boundary, cfg.rounds - 1)
        if (self.checkpointer is not None
                and self.checkpointer.policy.every_rounds is not None):
            n = self.checkpointer.policy.every_rounds
            next_save = (rnd // n + 1) * n - 1  # min r >= rnd, (r+1) % n == 0
            boundary = min(boundary, next_save)
        return max(1, min(cfg.fuse_rounds, boundary - rnd + 1))

    def _unpack(self, rnd: int, length: int, masks, finals, sel_losses,
                arrivals, fkind, fu) -> list[RoundResult]:
        """The ``RoundResult`` of each round of the chunk that starts at
        round ``rnd``, from its read-back outputs, with the comm, clock
        and fault ledgers advanced round by round and the chunk-final
        evaluation."""
        cfg = self.cfg
        results = []
        for i in range(length):
            r = rnd + i
            sel = np.where(masks[i])[0]
            surv = np.where(finals[i])[0]
            n_faulty = n_quarantined = 0
            uploaded: float | None = None
            if self._faults is not None:
                # per-round ledger replay off the scanned outputs:
                # arrivals feed the health record, the host-side
                # decisions give ground-truth fault counts + the
                # partial-upload byte fractions
                arr = np.where(arrivals[i])[0]
                flagged = np.where(arrivals[i] & ~finals[i])[0]
                self._faults.health.record(r, arr, flagged)
                kind_r = np.where(arrivals[i], fkind[i], -1)
                n_faulty = int((kind_r >= 0).sum())
                n_quarantined = self._faults.health.n_quarantined(r)
                uploaded = float(
                    self._faults.upload_fractions(
                        kind_r[arr], fu[i][arr]
                    ).sum()
                )
            if self._systems is not None:
                # same accounting core as the eager loop's outcome()
                out = self._systems.outcome_from_mask(r, masks[i])
                self.comm_mb += self.comm.round_mb(
                    out.n_reached, self.strategy.needs_losses,
                    m_uploaded=(
                        len(surv) if uploaded is None else uploaded
                    ),
                )
                self.sim_clock += out.sim_time
                sim_time, n_dropped = out.sim_time, out.n_dropped
                keep = finals[i][sel]  # survivor slots in cohort order
                mean_loss = _mean_loss(sel_losses[i][keep])
            elif self._faults is not None:
                self.comm_mb += self.comm.round_mb(
                    len(sel), self.strategy.needs_losses,
                    m_uploaded=uploaded,
                )
                sim_time, n_dropped = 0.0, 0
                keep = finals[i][sel]
                mean_loss = _mean_loss(sel_losses[i][keep])
            else:
                self.comm_mb += self.comm.round_mb(
                    len(sel), self.strategy.needs_losses
                )
                sim_time, n_dropped = 0.0, 0
                mean_loss = _mean_loss(sel_losses[i])
            test_loss = test_acc = metrics = None
            # same absolute cadence as Engine.rounds(): eval-due
            # rounds are always chunk-final (see _chunk_len), so the
            # committed params are exactly the eager loop's
            if i == length - 1 and (
                r % cfg.eval_every == 0 or r == cfg.rounds - 1
            ):
                with span("evaluate"):
                    test_loss, test_acc = self.evaluate()
                    metrics = self.eval_metrics()
            results.append(RoundResult(
                round=r,
                selected=tuple(int(j) for j in surv),
                mean_selected_loss=mean_loss,
                comm_mb=float(self.comm_mb),
                test_loss=test_loss,
                test_acc=test_acc,
                sim_time=float(sim_time),
                sim_clock=float(self.sim_clock),
                n_dropped=int(n_dropped),
                metrics=metrics,
                params_version=r + 1,
                n_faulty=int(n_faulty),
                n_quarantined=int(n_quarantined),
            ))
        return results

    # -- the fused round loop ------------------------------------------
    def rounds(
        self,
        n_rounds: int | None = None,
        callback=None,
    ) -> Iterator[RoundResult]:
        """Stream one ``RoundResult`` per round, computed chunk-at-a-time
        on device.  Same record semantics as ``Engine.rounds()``; state
        commits per chunk (see module docstring)."""
        cfg = self.cfg
        if n_rounds is None:
            n_rounds = max(cfg.rounds - self._round, 0)
        key = self._carry_key()
        start = self._round
        end = start + n_rounds
        rnd = start
        while rnd < end:
            # the chunk's inputs and its dispatch; the read-backs,
            # unpacking and evaluation follow in spans of their own
            with step("chunk", rnd):
                length = self._chunk_len(rnd, end)
                run = self._chunk_step(length)
                fkind = fu = None
                inputs: dict[str, np.ndarray] = {}
                if self._systems is not None:
                    # exogenous availability / deadline-arrival traces for
                    # the chunk (host-deterministic per round, so the fused
                    # run sees exactly what the eager backends see)
                    inputs["avail"] = np.stack(
                        [self._systems.available(rnd + i) for i in range(length)]
                    )
                    inputs["arrived"] = np.stack(
                        [self._systems.arrived(rnd + i) for i in range(length)]
                    )
                if self._faults is not None:
                    # per-round fault decisions are host-deterministic too;
                    # the admission gate is evaluated against the health
                    # ledger at *chunk start* — a fault flagged mid-chunk
                    # starts its quarantine at the next chunk boundary
                    # (eager runs quarantine one round earlier; DESIGN.md
                    # §14 documents the chunk-granular lag)
                    inputs["admit"] = np.stack(
                        [self._faults.health.admitted(rnd + i) for i in range(length)]
                    )
                    decisions = [self._faults.decide(rnd + i) for i in range(length)]
                    fkind = np.stack([k for k, _ in decisions])
                    fu = np.stack([u for _, u in decisions])
                    inputs["fkind"] = fkind
                    inputs["fu"] = fu
                if inputs:
                    outs = run(
                        self.params, key,
                        {k: jnp.asarray(v) for k, v in inputs.items()},
                    )
                else:
                    outs = run(self.params, key)
            if self._faults is not None:
                params, key, masks, finals, sel_losses, arrivals = outs
                arrivals = to_host(arrivals)
            else:
                params, key, masks, finals, sel_losses = outs
                arrivals = None
            # commit the chunk before yielding anything from it
            self.params, self._key = params, key
            self._round = rnd + length
            masks = to_host(masks)
            finals = to_host(finals)
            sel_losses = to_host(sel_losses)
            with span("unpack"):
                results = self._unpack(rnd, length, masks, finals, sel_losses,
                                       arrivals, fkind, fu)
            rnd += length
            for i, result in enumerate(results):
                # checkpoints only at the chunk-final round: the engine
                # state committed above is the *chunk-end* state, so a
                # mid-chunk save would pair end-of-chunk params with a
                # truncated history.  _chunk_len aligns round-trigger
                # save points to chunk boundaries, so no save is lost.
                self._emit(result, callback,
                           allow_save=(i == len(results) - 1))
                yield result
