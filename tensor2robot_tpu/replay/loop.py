"""The closed online loop: actors -> replay -> learner -> policy -> actors.

`OnlineLoop` wires the whole QT-Opt topology out of the pieces the repo
already has — research env actors (`replay/actor.py`), the replay
service (`replay/service.py`), the learner (`train/train_eval.py` over
a `ReplayInputGenerator`), the export path (`export/exporters.py`) and
the serving fleet (`serving/router.py`) — in two shapes:

  * **multi-process** (the default; `bench.py rl` and the slow soak):
    replay service + actor processes, optionally a FleetRouter over
    policy-server replicas with the RouterGateway feeding actors real
    fleet predictions; the learner runs in the driver and PUBLISHES a
    fresh policy at every checkpoint (export -> rolling fleet swap ->
    staleness anchor bump). Every process is individually SIGKILL-able,
    which is the point.
  * **in-process** (`in_process=True`; the tier-1 chaos twin): the same
    loop with the buffer in-process, actors as threads and a local
    policy client — every chaos site (`append`/`seal`/`sample`/
    `actor_step`/`publish_policy`) still fires, every counter still
    counts, no subprocess spend.

Policy publication rides the trainer's `after_checkpoint_saved` hook
(`PublishPolicyHook`): fires the `publish_policy` chaos site, exports
the current weights as a new artifact version, rolls the serving fleet
onto it, and advances the replay buffer's staleness anchor. Version
arithmetic is in *publishes* (1, 2, 3, ...): artifact model_versions
(timestamp dir names) are translated at the gateway so staleness is
always "how many publishes behind", not a timestamp delta.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from tensor2robot_tpu.hooks.hook_builder import Hook, HookBuilder
from tensor2robot_tpu.replay.actor import (
    EpisodeCollector,
    LocalPolicyClient,
    RandomPolicyClient,
    RouterGateway,
    actor_main,
)
from tensor2robot_tpu import flags as t2r_flags
from tensor2robot_tpu.replay.input_generator import ReplayInputGenerator
from tensor2robot_tpu.replay.service import (
    ReplayBuffer,
    ReplayServiceHandle,
)
from tensor2robot_tpu.replay.sharded import (
    ShardedReplayClient,
    ShardedReplayService,
    local_shard_backends,
    shard_root,
)
from tensor2robot_tpu.testing import chaos
from tensor2robot_tpu.utils.errors import best_effort

_log = logging.getLogger(__name__)

__all__ = ["LoopReport", "OnlineLoop", "PublishPolicyHook"]


@dataclasses.dataclass
class LoopReport:
    """What one loop run measured (the bench leg's raw material)."""

    learner_steps: int = 0
    episodes_appended: int = 0
    records_appended: int = 0
    samples_drawn: int = 0
    segments_sealed: int = 0
    episodes_lost: int = 0
    records_lost: int = 0
    replay_ratio: float = 0.0
    staleness_mean: float = 0.0
    staleness_max: int = 0
    publishes: int = 0
    replay_restarts: int = 0
    actors_killed: int = 0
    wall_s: float = 0.0
    episodes_per_s: float = 0.0
    samples_per_s: float = 0.0
    actor_reports: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list
    )
    recovery: Dict[str, int] = dataclasses.field(default_factory=dict)
    # False when the post-run service stats read failed: the loss/sample
    # counters above are then absent, not zero — acceptance gates must
    # treat the run as unmeasured, never as lossless.
    stats_ok: bool = True
    # Serving-degradation split (distinct meanings that used to share a
    # -1 stamp): fallback = the fleet never answered, the action is
    # random; version-unknown = a REAL fleet action whose publish age
    # could not be determined. Counted separately across all actors.
    fallback_actions: int = 0
    version_unknown_actions: int = 0
    # Sharded-fabric accounting (empty/zero for the single service).
    shards: int = 1
    per_shard: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list
    )
    coverage_lost_draws: List[int] = dataclasses.field(
        default_factory=list
    )
    spill_replayed: int = 0
    spill_dropped_episodes: int = 0
    appends_deduped: int = 0
    shards_unreachable: List[int] = dataclasses.field(
        default_factory=list
    )

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class PublishPolicyHook(Hook):
    """after_checkpoint_saved -> chaos site + export + fleet swap + anchor.

    `publish_fn(step, state) -> int` does the mode-specific work and
    returns the new publish counter; the hook only owns the chaos site
    and failure containment (a failed publish is logged and counted —
    the learner must keep training on the old policy, not die)."""

    def __init__(self, publish_fn: Callable[[int, Any], int]):
        self._publish_fn = publish_fn
        self.publishes = 0
        self.failures = 0

    def after_checkpoint_saved(self, ctx) -> None:
        try:
            # Chaos site INSIDE the containment: an injected fault here
            # is a publish-path fault (export died, fleet swap failed)
            # and must be survived exactly like a real one. A `kill`
            # clause still takes the whole learner down — that is the
            # learner-preemption fault, pinned separately.
            chaos.maybe_fire("publish_policy")
            self.publishes = self._publish_fn(ctx.step, ctx.state)
        except Exception:
            self.failures += 1
            _log.exception(
                "policy publish at step %d failed; actors keep the "
                "previous version", ctx.step,
            )


class _PublishHookBuilder(HookBuilder):
    """Hands the trainer's CompiledModel to the loop (the export path
    traces its predict step) and installs the publish hook."""

    def __init__(
        self,
        hook: PublishPolicyHook,
        on_trainer: Optional[Callable[[Any], None]] = None,
    ):
        self._hook = hook
        self._on_trainer = on_trainer

    def create_hooks(self, t2r_model, trainer=None) -> List[Hook]:
        del t2r_model
        if self._on_trainer is not None:
            self._on_trainer(trainer)
        return [self._hook]


class OnlineLoop:
    """Harness for the closed loop; the caller owns pacing and chaos.

    Typical use (multi-process):

        loop = OnlineLoop(root, num_actors=2, use_router=True).start()
        loop.run_learner(max_steps=30, save_steps=10)  # blocks
        report = loop.stop()

    Chaos controls for the bench/suites: `kill_replay_service()` and
    `kill_actor(i)` SIGKILL live processes mid-run (the service handle
    respawns the service; a killed actor stays dead and is counted).
    """

    def __init__(
        self,
        root: str,
        num_actors: int = 2,
        episodes_per_actor: int = 0,  # 0 = collect until stopped
        batch_size: int = 8,
        seal_episodes: int = 4,
        seal_bytes: Optional[int] = None,
        sampler: Optional[str] = None,
        seed: int = 7,
        in_process: bool = False,
        use_router: bool = False,
        router: Any = None,
        binary_success_threshold: float = -0.35,
        model_fn: Optional[Callable[[], Any]] = None,
        wait_timeout_s: float = 120.0,
        actor_throttle_s: float = 0.0,
        shards: Optional[int] = None,
        transport: Optional[str] = None,
    ):
        self.root = root
        self.replay_root = os.path.join(root, "replay")
        self.model_dir = os.path.join(root, "learner")
        self.export_dir = self.model_dir  # exporters nest export/ inside
        self.num_actors = num_actors
        self.episodes_per_actor = episodes_per_actor
        self.batch_size = batch_size
        self.seal_episodes = seal_episodes
        self.seal_bytes = seal_bytes
        self.sampler = sampler
        self.seed = seed
        self.in_process = in_process
        self.use_router = use_router
        # Sharded topology: >1 = consistent-hash placement over N shard
        # services (replay/sharded.py); the transport picks the wire
        # (socket = the cross-host fabric, queue = single-host default).
        self.shards = (
            t2r_flags.get_int("T2R_REPLAY_SHARDS")
            if shards is None else max(1, shards)
        )
        self.transport = transport
        self._router = router
        self._threshold = binary_success_threshold
        self._model_fn = model_fn or self._default_model_fn
        self._wait_timeout_s = wait_timeout_s
        self._actor_throttle_s = actor_throttle_s

        self._service: Optional[ReplayServiceHandle] = None
        self._sharded: Optional[ShardedReplayService] = None
        self._sharded_client: Optional[ShardedReplayClient] = None
        self._shard_buffers: List[ReplayBuffer] = []
        self._buffer: Optional[ReplayBuffer] = None
        self._gateway: Optional[RouterGateway] = None
        self._actor_processes: List[Any] = []
        self._actor_threads: List[threading.Thread] = []
        self._actor_stop = threading.Event()
        self._actor_stop_event = None  # mp.Event, multi-process modes
        self._report_q = None
        self._publish_hook: Optional[PublishPolicyHook] = None
        self._version_counter = 0
        self._version_translate: Dict[int, int] = {}
        self._exporter = None
        self._compiled_for_export = None
        self._driver_client = None
        self._learner_client = None
        self._generator: Optional[ReplayInputGenerator] = None
        self._learner_steps = 0
        self._actors_killed = 0
        self._t_start = 0.0
        self._in_process_episodes = 0

    @staticmethod
    def _default_model_fn():
        from tensor2robot_tpu.research.pose_env.pose_env_models import (
            PoseEnvRegressionModel,
        )

        return PoseEnvRegressionModel()

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> "OnlineLoop":
        os.makedirs(self.replay_root, exist_ok=True)
        self._t_start = time.monotonic()
        if self.in_process:
            self._start_in_process()
        else:
            self._start_multi_process()
        return self

    def _start_in_process(self) -> None:
        if self.shards > 1:
            # The tier-1 sharded twin: N in-process buffers behind the
            # SAME placement/failover/counting client the multi-process
            # fabric uses — every sharded code path, zero subprocesses.
            self._shard_buffers = [
                ReplayBuffer(
                    shard_root(self.replay_root, shard),
                    seal_episodes=self.seal_episodes,
                    seal_bytes=self.seal_bytes,
                    sampler=self.sampler,
                    seed=self.seed,
                )
                for shard in range(self.shards)
            ]
            self._sharded_client = ShardedReplayClient(
                local_shard_backends(self._shard_buffers),
                client_id="loop",
                seed=self.seed,
            )
        else:
            self._buffer = ReplayBuffer(
                self.replay_root,
                seal_episodes=self.seal_episodes,
                seal_bytes=self.seal_bytes,
                sampler=self.sampler,
                seed=self.seed,
            )

        def actor_thread(index: int) -> None:
            from tensor2robot_tpu.research.pose_env.pose_env import (
                PoseToyEnv,
            )

            policy = self._local_policy_client(seed=self.seed + index)
            env = PoseToyEnv(seed=self.seed + index)
            collector = EpisodeCollector(
                env, policy, binary_success_threshold=self._threshold
            )
            sink = self._sharded_client or self._buffer
            episodes = 0
            while not self._actor_stop.is_set() and (
                self.episodes_per_actor == 0
                or episodes < self.episodes_per_actor
            ):
                records, info = collector.collect()
                sink.append(
                    records,
                    policy_version=max(info["policy_version"], 0),
                    priority=info["priority"],
                )
                episodes += 1
                self._in_process_episodes += 1
                if self._actor_throttle_s:
                    self._actor_stop.wait(self._actor_throttle_s)

        for index in range(self.num_actors):
            thread = threading.Thread(
                target=actor_thread, args=(index,), daemon=True
            )
            thread.start()
            self._actor_threads.append(thread)

    def _local_policy_client(self, seed: int):
        """In-process actors read the loop's published version; actions
        stay random (the in-process twin tests the PLUMBING — append/
        seal/sample/publish/staleness — not fleet serving)."""
        random_client = RandomPolicyClient(seed=seed)

        loop = self

        class _Client:
            def act(self, obs):
                action, _ = random_client.act(obs)
                return action, loop._version_counter

        return _Client()

    def _start_multi_process(self) -> None:
        client_ids = [f"actor-{i}" for i in range(self.num_actors)] + [
            "learner", "driver",
        ]
        config = {
            "seal_episodes": self.seal_episodes,
            "seal_bytes": self.seal_bytes,
            "sampler": self.sampler,
            "seed": self.seed,
        }
        if self.shards > 1:
            self._sharded = ShardedReplayService(
                self.replay_root,
                self.shards,
                client_ids,
                config=config,
                transport=self.transport,
            ).start()
            mp_ctx = self._sharded.handles[0]._ctx
        else:
            self._service = ReplayServiceHandle(
                self.replay_root,
                client_ids,
                config=config,
                transport=self.transport,
            ).start()
            mp_ctx = self._service._ctx
        gateway_queue_pairs: List[Any] = [None] * self.num_actors
        if self.use_router:
            if self._router is None:
                raise ValueError(
                    "use_router=True needs a started FleetRouter passed "
                    "as router= (the loop does not own fleet lifecycle)"
                )
            actor_ids = [f"actor-{i}" for i in range(self.num_actors)]
            self._gateway = RouterGateway(
                self._router,
                actor_ids,
                mp_context=mp_ctx,
                version_translate=self._version_translate,
            ).start()
            gateway_queue_pairs = [
                self._gateway.actor_queues(actor_id)
                for actor_id in actor_ids
            ]
        self._report_q = mp_ctx.Queue()
        self._actor_stop_event = mp_ctx.Event()
        for index in range(self.num_actors):
            replay_kwargs: Dict[str, Any] = (
                {"shard_specs": self._sharded.client_specs(
                    f"actor-{index}")}
                if self._sharded is not None
                else {"replay_queues": self._service.client_queues(
                    f"actor-{index}")}
            )
            process = mp_ctx.Process(
                target=actor_main,
                kwargs=dict(
                    actor_id=index,
                    gateway_queues=gateway_queue_pairs[index],
                    num_episodes=self.episodes_per_actor,
                    seed=self.seed + index,
                    binary_success_threshold=self._threshold,
                    report_q=self._report_q,
                    throttle_s=self._actor_throttle_s,
                    stop_event=self._actor_stop_event,
                    **replay_kwargs,
                ),
                daemon=True,
            )
            process.start()
            self._actor_processes.append(process)

    def register_artifact_version(
        self, model_version: int, publish_counter: int = 0
    ) -> None:
        """Maps a pre-existing artifact's model_version (the bootstrap
        export the fleet booted on) to a publish counter, so episodes
        collected before the first publish stamp 0, not a timestamp."""
        self._version_translate[int(model_version)] = publish_counter

    # -- chaos controls --------------------------------------------------------

    def kill_replay_service(self) -> Optional[int]:
        if self._sharded is not None:
            return self.kill_shard(0)
        if self._service is None:
            raise RuntimeError("no replay service in in-process mode")
        return self._service.kill()

    def kill_shard(self, shard: int) -> Optional[int]:
        """SIGKILL one shard's service process (its supervisor respawns
        it); the fabric spills/fails over meanwhile — that is the leg."""
        if self._sharded is None:
            raise RuntimeError("no sharded replay service in this mode")
        return self._sharded.kill_shard(shard)

    def kill_actor(self, index: int) -> Optional[int]:
        process = self._actor_processes[index]
        if not process.is_alive():
            return None
        pid = process.pid
        os.kill(pid, 9)
        self._actors_killed += 1
        return pid

    # -- the learner -----------------------------------------------------------

    def _publish(self, step: int, state) -> int:
        """Export the current weights, roll the fleet, bump the anchor."""
        self._version_counter += 1
        if self._exporter is not None and not self.in_process:
            path = self._exporter.maybe_export(
                step=step,
                state=state,
                eval_metrics={"loss": 0.0},
                compiled=self._compiled_for_export,
                model_dir=self.model_dir,
            )
            if path is not None:
                base = os.path.basename(path.rstrip("/"))
                if base.isdigit():
                    self._version_translate[int(base)] = (
                        self._version_counter
                    )
            if self._router is not None:
                self._router.rolling_swap()
        if self._buffer is not None:
            self._buffer.set_policy_version(self._version_counter)
        elif self._sharded_client is not None:
            self._sharded_client.set_policy_version(self._version_counter)
        elif self._service is not None or self._sharded is not None:
            self._driver().set_policy_version(self._version_counter)
        return self._version_counter

    def _driver(self):
        """ONE long-lived driver client: a fresh client per call would
        share the response queue with its predecessors (reply aliasing
        is guarded by opaque tokens, but one instance is simply right)."""
        if self._driver_client is None:
            if self._sharded is not None:
                self._driver_client = self._sharded.client("driver")
            else:
                self._driver_client = self._service.client(
                    "driver", timeout_s=10.0, retries=3
                )
        return self._driver_client

    def run_learner(
        self,
        max_steps: int = 20,
        save_steps: int = 10,
        publish: bool = True,
        export_buckets=(1,),
        learner_kwargs: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, float]:
        """Blocks training the learner over replay samples; publishes at
        every checkpoint when `publish`."""
        from tensor2robot_tpu.train import train_eval as te

        model = self._model_fn()
        if self._sharded is not None:
            client: Any = self._sharded.client("learner")
        elif self._sharded_client is not None:
            client = self._sharded_client  # in-process sharded twin
        elif self._service is not None:
            client = self._service.client("learner", timeout_s=30.0)
        else:
            client = None
        self._learner_client = client
        self._generator = ReplayInputGenerator(
            self.replay_root,
            batch_size=self.batch_size,
            client=client,
            wait_timeout_s=self._wait_timeout_s,
            refresh=client is None,
            staleness_anchor=(
                (lambda: self._version_counter) if client is None else None
            ),
        )
        hook_builders = []
        if publish:
            from tensor2robot_tpu.export.exporters import LatestExporter

            if not self.in_process:
                self._exporter = LatestExporter(
                    name="latest",
                    warmup_batch_sizes=tuple(export_buckets),
                )
            self._publish_hook = PublishPolicyHook(self._publish)

            def on_trainer(trainer):
                self._compiled_for_export = trainer

            hook_builders.append(
                _PublishHookBuilder(self._publish_hook, on_trainer)
            )
        final = te.train_eval_model(
            model,
            input_generator_train=self._generator,
            model_dir=self.model_dir,
            max_train_steps=max_steps,
            eval_steps=None,
            save_checkpoints_steps=save_steps,
            log_every_steps=max(save_steps, 1),
            seed=self.seed,
            hook_builders=hook_builders,
            **(learner_kwargs or {}),
        )
        # The step the learner ACTUALLY reached, read off the final
        # durable checkpoint (train_eval_model blesses it at exit) —
        # never assume max_steps: the bench acceptance gate compares
        # this across the chaos/fault-free twins, and a silently
        # under-trained leg must FAIL that gate, not sail through.
        from tensor2robot_tpu.train import durability

        actual = durability.latest_durable_step(self.model_dir)
        self._learner_steps = actual if actual is not None else 0
        return final

    # -- teardown + report -----------------------------------------------------

    def _merge_fabric_counters(
        self, report: LoopReport, client: ShardedReplayClient
    ) -> None:
        self._merge_fabric_counter_dict(report, client.counters)

    @staticmethod
    def _merge_fabric_counter_dict(
        report: LoopReport, counters: Dict[str, Any]
    ) -> None:
        """Folds one sharded client's degradation counters into the
        report — every client (each actor's, the learner's) keeps its
        own, and the fabric-wide number is their sum."""
        if not counters:
            return
        report.spill_replayed += counters.get("spill_replayed", 0)
        report.spill_dropped_episodes += counters.get(
            "spill_dropped_episodes", 0
        )
        report.appends_deduped += counters.get("appends_deduped", 0)
        lost = counters.get("coverage_lost_draws") or []
        if not report.coverage_lost_draws:
            report.coverage_lost_draws = [0] * len(lost)
        for shard, count in enumerate(lost):
            if shard < len(report.coverage_lost_draws):
                report.coverage_lost_draws[shard] += count

    def stop(self, timeout_s: float = 30.0) -> LoopReport:
        report = LoopReport()
        report.wall_s = time.monotonic() - self._t_start
        report.learner_steps = self._learner_steps
        report.actors_killed = self._actors_killed
        report.shards = self.shards
        if self._publish_hook is not None:
            report.publishes = self._publish_hook.publishes
        self._actor_stop.set()
        for thread in self._actor_threads:
            thread.join(timeout_s)
        stats: Dict[str, Any] = {}
        if self._buffer is not None:
            stats = self._buffer.stats()
            self._buffer.close(seal_tail=True)
        if self._sharded_client is not None:
            # In-process sharded twin: the shared client holds the
            # fabric counters; seal + close the buffers it fronts.
            stats = self._sharded_client.stats()
            self._merge_fabric_counters(report, self._sharded_client)
            for buffer in self._shard_buffers:
                buffer.close(seal_tail=True)
        if self._service is not None or self._sharded is not None:
            # Cooperative actor drain FIRST: the stop event lets each
            # actor finish its in-flight episode, flush any spill, and
            # post its report (spill/fallback counters) before the
            # hard-terminate backstop below.
            if self._actor_stop_event is not None:
                self._actor_stop_event.set()
            for process in self._actor_processes:
                process.join(3.0)
            try:
                if self._sharded is not None:
                    # A shard SIGKILLed moments before stop() is mid-
                    # respawn right now; give each supervisor a bounded
                    # window to republish before the stats read calls
                    # it unreachable (stats_ok=False is for shards that
                    # STAY dark, not for losing a boot race).
                    for handle in self._sharded.handles:
                        handle.wait_ready(10.0)
                stats = self._driver().stats()
                if self._sharded is not None and stats.get(
                    "shards_unreachable"
                ):
                    # Partial totals are not measured totals: a shard
                    # whose counters could not be read means every
                    # summed gate below would under-count.
                    report.stats_ok = False
                    report.shards_unreachable = list(
                        stats["shards_unreachable"]
                    )
            except Exception:
                # NOT silently zeroed: fabricated-zero loss counters
                # would pass every acceptance gate. The report says the
                # stats read itself failed; gates must check stats_ok.
                _log.exception("post-run replay stats read failed")
                stats = {}
                report.stats_ok = False
            report.replay_restarts = (
                self._sharded.respawns
                if self._sharded is not None
                else self._service.respawns
            )
            for process in self._actor_processes:
                if process.is_alive():
                    process.terminate()
                    process.join(5.0)
            if self._report_q is not None:
                while True:
                    try:
                        report.actor_reports.append(
                            self._report_q.get_nowait()
                        )
                    except Exception:
                        break
            if self._sharded is not None:
                self._sharded.stop()
            else:
                self._service.stop()
        if (
            isinstance(self._learner_client, ShardedReplayClient)
            and self._learner_client is not self._sharded_client
        ):
            self._merge_fabric_counters(report, self._learner_client)
        for actor_report in report.actor_reports:
            report.fallback_actions += actor_report.get(
                "fallback_actions", 0
            )
            report.version_unknown_actions += actor_report.get(
                "version_unknown_actions", 0
            )
            counters = actor_report.get("replay_counters") or {}
            self._merge_fabric_counter_dict(report, counters)
        if self._gateway is not None:
            self._gateway.stop()
        if stats:
            report.episodes_appended = stats.get(
                "episodes_appended_total", 0
            )
            report.records_appended = stats.get("records_appended_total", 0)
            report.samples_drawn = stats.get("samples_drawn", 0)
            report.segments_sealed = stats.get("segments_sealed", 0)
            report.episodes_lost = stats.get("episodes_lost_total", 0)
            report.records_lost = stats.get("records_lost_total", 0)
            report.replay_ratio = stats.get("replay_ratio", 0.0)
            staleness = stats.get("staleness_last", {})
            report.staleness_mean = staleness.get("staleness_mean", 0.0)
            report.staleness_max = int(stats.get("staleness_max_seen", 0))
            report.recovery = stats.get("recovery", {})
            per_shard = stats.get("per_shard")
            if per_shard is not None:
                report.per_shard = [dict(entry) for entry in per_shard]
                # Fabric-level recovery/staleness: sum the shards'
                # recovery sweeps; take the worst staleness any shard
                # has seen (a partitioned shard's lag must not average
                # away).
                merged_recovery: Dict[str, int] = {}
                for entry in report.per_shard:
                    for key, value in (entry.get("recovery") or {}).items():
                        merged_recovery[key] = (
                            merged_recovery.get(key, 0) + value
                        )
                    report.staleness_max = max(
                        report.staleness_max,
                        int(entry.get("staleness_max_seen", 0)),
                    )
                report.recovery = merged_recovery
        if self.in_process:
            report.episodes_appended = max(
                report.episodes_appended, self._in_process_episodes
            )
        if self._generator is not None and self._generator.batches_drawn:
            # Dir-mode sampling happens in the learner's generator, not
            # the buffer — its counters are the truth there; in service
            # mode they cross-check the service's.
            drawn = self._generator.batches_drawn * self.batch_size
            report.samples_drawn = max(report.samples_drawn, drawn)
            if report.records_appended:
                report.replay_ratio = (
                    report.samples_drawn / report.records_appended
                )
            staleness = self._generator.last_staleness
            if staleness:
                report.staleness_mean = staleness.get(
                    "staleness_mean", report.staleness_mean
                )
                report.staleness_max = max(
                    report.staleness_max,
                    int(staleness.get("staleness_max", 0)),
                )
        if report.wall_s > 0:
            report.episodes_per_s = (
                report.episodes_appended / report.wall_s
            )
            report.samples_per_s = report.samples_drawn / report.wall_s
        return report
