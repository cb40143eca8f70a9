"""System scheduler: one alloc per eligible node
(reference scheduler/system_sched.go).
"""
from __future__ import annotations

from dataclasses import replace as _replace
from typing import Dict, List, Optional

from ..structs import (
    ALLOC_CLIENT_STATUS_LOST,
    ALLOC_CLIENT_STATUS_PENDING,
    ALLOC_DESIRED_RUN,
    AllocatedResources,
    AllocatedSharedResources,
    Allocation,
    AllocMetric,
    Evaluation,
    EVAL_STATUS_COMPLETE,
    EVAL_STATUS_FAILED,
    filter_terminal_allocs,
    Node,
    Plan,
    PlanResult,
)
from .context import EvalContext
from .rank import flush_fit_counts
from .reconcile import (
    ALLOC_LOST,
    ALLOC_NODE_TAINTED,
    ALLOC_NOT_NEEDED,
    ALLOC_UPDATING,
    BLOCKED_EVAL_FAILED_PLACEMENTS,
)
from .scheduler import SetStatusError
from .stack import SystemStack
from .util import (
    adjust_queued_allocations,
    diff_system_allocs,
    evict_and_place,
    inplace_update,
    progress_made,
    ready_nodes_in_dcs,
    retry_max,
    set_status,
    tainted_nodes,
    update_non_terminal_allocs_to_lost,
)

MAX_SYSTEM_SCHEDULE_ATTEMPTS = 5

SUPPORTED_TRIGGERS = {
    "job-register",
    "node-update",
    "failed-follow-up",
    "job-deregister",
    "rolling-update",
    "preemption",
    "deployment-watcher",
    "node-drain",
    "alloc-stop",
    "queued-allocs",
    "job-scaling",
}


class SystemScheduler:
    def __init__(
        self, state, planner, use_tpu: Optional[bool] = None,
        seed: Optional[int] = None,
    ) -> None:
        self.state = state
        self.planner = planner
        self.seed = seed
        if use_tpu is None:
            use_tpu = state.scheduler_config().tpu_scheduler_enabled
        self.use_tpu = use_tpu

        self.eval: Optional[Evaluation] = None
        self.job = None
        self.plan: Optional[Plan] = None
        self.plan_result: Optional[PlanResult] = None
        self.ctx: Optional[EvalContext] = None
        self.stack = None
        self.nodes: List[Node] = []
        self.nodes_by_dc: Dict[str, int] = {}
        self.limit_reached = False
        self.next_eval: Optional[Evaluation] = None
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}

    def process(self, evaluation: Evaluation) -> None:
        self.eval = evaluation
        if evaluation.triggered_by not in SUPPORTED_TRIGGERS:
            desc = (
                f"scheduler cannot handle '{evaluation.triggered_by}' "
                "evaluation reason"
            )
            set_status(
                self.planner, evaluation, self.next_eval, None,
                self.failed_tg_allocs, EVAL_STATUS_FAILED, desc,
                self.queued_allocs, "",
            )
            return
        try:
            retry_max(
                MAX_SYSTEM_SCHEDULE_ATTEMPTS,
                self._process_once,
                lambda: progress_made(self.plan_result),
            )
        except SetStatusError as err:
            set_status(
                self.planner, self.eval, self.next_eval, None,
                self.failed_tg_allocs, err.eval_status, str(err),
                self.queued_allocs, "",
            )
            return
        set_status(
            self.planner, self.eval, self.next_eval, None,
            self.failed_tg_allocs, EVAL_STATUS_COMPLETE, "",
            self.queued_allocs, "",
        )

    def _process_once(self) -> bool:
        self.job = self.state.job_by_id(
            self.eval.namespace, self.eval.job_id
        )
        self.queued_allocs = {}

        if self.job is not None and not self.job.stopped():
            self.nodes, self.nodes_by_dc = ready_nodes_in_dcs(
                self.state, self.job.datacenters
            )
        else:
            self.nodes, self.nodes_by_dc = [], {}

        self.plan = self.eval.make_plan(self.job)
        self.failed_tg_allocs = {}
        self.ctx = EvalContext(self.state, self.plan, seed=self.seed)
        self.stack = self._make_stack()
        if self.job is not None and not self.job.stopped():
            self.stack.set_job(self.job)

        try:
            self._compute_job_allocs()
        finally:
            flush_fit_counts(self.ctx)

        if self.plan.is_no_op() and not self.eval.annotate_plan:
            return True

        if self.limit_reached and self.next_eval is None:
            stagger = (
                self.job.update.stagger_s
                if self.job is not None and self.job.update is not None
                else 30.0
            )
            self.next_eval = self.eval.next_rolling_eval(stagger)
            self.planner.create_eval(self.next_eval)

        result, new_state = self.planner.submit_plan(self.plan)
        self.plan_result = result
        adjust_queued_allocations(result, self.queued_allocs)

        if new_state is not None:
            self.state = new_state
            return False
        full_commit, _e, _a = result.full_commit(self.plan)
        if not full_commit:
            return False
        return True

    def _make_stack(self):
        if self.use_tpu:
            from .tpu_stack import TPUSystemStack

            return TPUSystemStack(self.ctx, seed=self.seed)
        return SystemStack(self.ctx)

    def _compute_job_allocs(self) -> None:
        allocs = self.state.allocs_by_job(
            self.eval.namespace, self.eval.job_id
        )
        tainted = tainted_nodes(self.state, allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, allocs)

        live, terminal = filter_terminal_allocs(allocs)

        if self.job is None:
            from ..structs import Job

            job_for_diff = Job(id=self.eval.job_id, stop=True)
        else:
            job_for_diff = self.job
        diff = diff_system_allocs(
            job_for_diff, self.nodes, tainted, live, terminal
        )

        for e in diff.stop:
            self.plan.append_stopped_alloc(e.alloc, ALLOC_NOT_NEEDED)
        for e in diff.migrate:
            self.plan.append_stopped_alloc(e.alloc, ALLOC_NODE_TAINTED)
        for e in diff.lost:
            self.plan.append_stopped_alloc(
                e.alloc, ALLOC_LOST, ALLOC_CLIENT_STATUS_LOST
            )

        destructive, _inplace = inplace_update(
            self.ctx, self.eval, self.job, self.stack, diff.update
        )
        diff.update = destructive

        limit = len(diff.update)
        if (
            self.job is not None
            and not self.job.stopped()
            and self.job.update is not None
            and self.job.update.max_parallel > 0
        ):
            limit = self.job.update.max_parallel
        limit_box = [limit]
        self.limit_reached = evict_and_place(
            self.ctx, diff, diff.update, ALLOC_UPDATING, limit_box
        )

        if not diff.place:
            if self.job is not None and not self.job.stopped():
                for tg in self.job.task_groups:
                    self.queued_allocs[tg.name] = 0
            return

        for tup in diff.place:
            self.queued_allocs[tup.task_group.name] = (
                self.queued_allocs.get(tup.task_group.name, 0) + 1
            )
        self._compute_placements(diff.place)

    def _compute_placements(self, place) -> None:
        import time as _time

        node_by_id = {node.id: node for node in self.nodes}
        for missing in place:
            node = node_by_id.get(missing.alloc.node_id)
            if node is None:
                continue
            self.stack.set_nodes([node])
            t_select = _time.monotonic()
            option = self.stack.select(missing.task_group, None)
            # per-TG allocation latency (AllocMetric.AllocationTime)
            self.ctx.metrics.allocation_time_s = (
                _time.monotonic() - t_select
            )

            if option is None:
                if self.ctx.metrics.nodes_filtered > 0:
                    self.queued_allocs[missing.task_group.name] -= 1
                    continue
                metric = self.failed_tg_allocs.get(missing.task_group.name)
                if metric is not None:
                    metric.coalesced_failures += 1
                    continue
                self.ctx.metrics.nodes_available = self.nodes_by_dc
                self.failed_tg_allocs[missing.task_group.name] = (
                    self.ctx.metrics
                )
                self._add_blocked(node)
                continue

            self.ctx.metrics.nodes_available = self.nodes_by_dc
            resources = AllocatedResources(
                tasks=option.task_resources,
                shared=AllocatedSharedResources(
                    disk_mb=missing.task_group.ephemeral_disk.size_mb
                ),
            )
            if option.alloc_resources is not None:
                resources.shared.networks = option.alloc_resources.networks
                resources.shared.ports = option.alloc_resources.ports

            alloc = Allocation(
                namespace=self.job.namespace,
                eval_id=self.eval.id,
                name=missing.name,
                job_id=self.job.id,
                job=self.job,
                task_group=missing.task_group.name,
                metrics=self.ctx.metrics,
                node_id=option.node.id,
                node_name=option.node.name,
                allocated_resources=resources,
                desired_status=ALLOC_DESIRED_RUN,
                client_status=ALLOC_CLIENT_STATUS_PENDING,
            )
            if missing.alloc is not None and missing.alloc.id:
                alloc.previous_allocation = missing.alloc.id

            if option.preempted_allocs is not None:
                for stop in option.preempted_allocs:
                    self.plan.append_preempted_alloc(stop, alloc.id)

            self.plan.append_alloc(alloc)

    def _add_blocked(self, node: Node) -> None:
        e = self.ctx.eligibility
        escaped = e.has_escaped()
        class_eligibility = {} if escaped else e.get_classes()
        blocked = self.eval.create_blocked_eval(
            class_eligibility, escaped, e.quota_reached
        )
        blocked.status_description = BLOCKED_EVAL_FAILED_PLACEMENTS
        blocked.node_id = node.id
        self.planner.create_eval(blocked)
