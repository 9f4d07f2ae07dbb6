"""Spec-driven gRPC stubs and servicer registration + the resilient RPC plane.

The image has protoc but no grpc python plugin, so instead of codegen'd
`*_pb2_grpc.py` files each service is declared once as a ServiceSpec table and
both the client stub and the server handler are built from it generically.
Method set mirrors the reference's Master and Pserver services
(/root/reference/elasticdl/proto/elasticdl.proto:108-157).

Every channel built here is hardened (docs/ROBUSTNESS.md):

- per-method deadlines: a stub call with no explicit timeout gets the
  method's default from METHOD_POLICIES, so no call site can hang forever
  on a wedged peer.
- retries: jittered exponential backoff on retryable statuses (UNAVAILABLE
  always; DEADLINE_EXCEEDED only for idempotent methods — a timed-out
  gradient push may have applied server-side and must not double-apply).
  INVALID_ARGUMENT and friends fail fast.
- circuit breaker: per-peer, trips after consecutive connectivity failures,
  fails fast while open, half-opens on a timer with a single probe.
- channel-readiness wait: build_channel TCP-probes the peer before opening
  the channel. A channel whose first connect attempt predates the peer's
  bind can wedge in UNAVAILABLE on sandboxed/virtualized network stacks
  (first observed in tools/elastic_drill.py with grpc 1.68 under the CI
  sandbox); probing first sidesteps the wedge for every client.
- fault injection: when a chaos schedule is configured (argument or the
  ELASTICDL_CHAOS env var), serve()/build_channel() install the
  elasticdl_tpu.chaos interceptors so drills can inject deterministic
  faults into real processes.

Retry/trip counts export through the process metrics registry:
edl_rpc_retries_total, edl_rpc_client_failures_total,
edl_rpc_breaker_trips_total, edl_rpc_breaker_fast_fail_total.
"""

import concurrent.futures
import dataclasses
import json
import random
import socket
import threading
import time

import grpc

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

logger = get_logger("common.rpc")

# Matches the reference's 256 MB gRPC message cap
# (/root/reference/elasticdl/python/common/constants.py:15-19).
MAX_MESSAGE_LENGTH = 256 * 1024 * 1024

GRPC_CHANNEL_OPTIONS = [
    ("grpc.max_send_message_length", MAX_MESSAGE_LENGTH),
    ("grpc.max_receive_message_length", MAX_MESSAGE_LENGTH),
    # Elasticity tuning: a relaunched peer (PS flap, worker preemption)
    # comes back in seconds, but grpc's default reconnect backoff climbs
    # to 20s+ — the channel would keep reporting UNAVAILABLE long after
    # the peer recovered, stretching every failover. Reconnect fast,
    # capped low; the retry plane's own jittered backoff paces the calls.
    ("grpc.initial_reconnect_backoff_ms", 250),
    ("grpc.min_reconnect_backoff_ms", 250),
    ("grpc.max_reconnect_backoff_ms", 5000),
]

_REG = default_registry()
_RETRIES = _REG.counter(
    "edl_rpc_retries_total",
    "RPC attempts retried after a retryable failure",
    labelnames=("method",),
)
_FAILURES = _REG.counter(
    "edl_rpc_client_failures_total",
    "Terminal client-side RPC failures (retries exhausted or fail-fast)",
    labelnames=("method", "code"),
)
_TRIPS = _REG.counter(
    "edl_rpc_breaker_trips_total",
    "Circuit-breaker trips (closed/half-open -> open)",
    labelnames=("peer",),
)
_FAST_FAILS = _REG.counter(
    "edl_rpc_breaker_fast_fail_total",
    "Calls rejected locally because the peer's circuit was open",
    labelnames=("peer",),
)


@dataclasses.dataclass(frozen=True)
class ServiceSpec:
    name: str
    # method name -> (request class, response class)
    methods: dict


MASTER_SERVICE = ServiceSpec(
    name="elasticdl_tpu.Master",
    methods={
        "get_task": (pb.GetTaskRequest, pb.Task),
        # Lease batching: up to max_tasks tasks per RPC, batched reports.
        "get_task_batch": (pb.GetTaskRequest, pb.TaskBatch),
        "report_task_result": (pb.ReportTaskResultRequest, pb.Empty),
        "report_task_results": (pb.ReportTaskResultsRequest, pb.Empty),
        "report_evaluation_metrics": (pb.ReportEvaluationMetricsRequest, pb.Empty),
        "report_version": (pb.ReportVersionRequest, pb.Empty),
        "get_comm_rank": (pb.GetCommRankRequest, pb.GetCommRankResponse),
        "lease_steps": (pb.LeaseStepsRequest, pb.LeaseStepsResponse),
        "report_lease": (pb.ReportLeaseRequest, pb.Empty),
        "report_worker_liveness": (pb.ReportWorkerLivenessRequest, pb.Empty),
        "get_job_status": (pb.GetJobStatusRequest, pb.JobStatusResponse),
        "start_profile": (pb.StartProfileRequest, pb.StartProfileResponse),
        "report_telemetry": (
            pb.ReportTelemetryRequest,
            pb.ReportTelemetryResponse,
        ),
        # Policy plane: workers poll the announced next world so the AOT
        # speculator compiles it instead of guessing N±delta.
        "get_world_hint": (pb.GetWorldHintRequest, pb.WorldHintResponse),
    },
)

# Rank-0 worker state broadcast for elastic AllReduce regroups (the Horovod
# broadcast_variables analog — see elasticdl_tpu/parallel/broadcast.py).
COLLECTIVE_SERVICE = ServiceSpec(
    name="elasticdl_tpu.Collective",
    methods={"pull_model": (pb.PullDenseParametersRequest, pb.Model)},
)

PSERVER_SERVICE = ServiceSpec(
    name="elasticdl_tpu.Pserver",
    methods={
        "push_model": (pb.Model, pb.Empty),
        "push_embedding_table_infos": (pb.Model, pb.Empty),
        "pull_dense_parameters": (
            pb.PullDenseParametersRequest,
            pb.PullDenseParametersResponse,
        ),
        "pull_embedding_vectors": (pb.PullEmbeddingVectorsRequest, pb.Tensor),
        "pull_embedding_table": (
            pb.PullEmbeddingTableRequest,
            pb.IndexedSlices,
        ),
        "push_gradients": (pb.PushGradientsRequest, pb.PushGradientsResponse),
        # Out-of-band transport: slim span header + one contiguous payload
        # blob (clients may send a duck-typed tensor_utils.PackedPushRequest
        # that appends the payload without copying it through a proto
        # object — the Stub serializer is duck-typed for exactly this).
        "push_gradients_packed": (
            pb.PushGradientsPackedRequest,
            pb.PushGradientsResponse,
        ),
    },
)


# ---------- retry policy ----------

_RETRYABLE = (
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
)
# Connectivity-only: non-idempotent methods must not replay a call that may
# have applied server-side before its deadline fired.
_RETRYABLE_CONNECTIVITY = (grpc.StatusCode.UNAVAILABLE,)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Deadline + retry classification for one RPC method."""

    deadline: float = 30.0
    max_attempts: int = 5
    backoff_base: float = 0.2
    backoff_multiplier: float = 2.0
    backoff_max: float = 5.0
    jitter: float = 0.5  # fraction of each backoff randomized away
    retryable_codes: tuple = _RETRYABLE

    def retryable(self, code):
        return code in self.retryable_codes

    def backoff(self, attempt, rng):
        """Sleep before retry number `attempt` (0-based). Full backoff minus
        a jittered fraction, so a fleet of workers hitting one restarted
        peer doesn't re-dogpile it in lockstep."""
        base = min(
            self.backoff_max,
            self.backoff_base * self.backoff_multiplier**attempt,
        )
        return base * (1.0 - self.jitter * rng.random())


# Per-method deadline/retry matrix (docs/ROBUSTNESS.md keeps the prose
# version). EVERY spec method must appear here — the rpc-deadlines rule
# of tools/edl_lint fails the lint lane otherwise.
METHOD_POLICIES = {
    # Master service: small control messages; get_task answers WAIT rather
    # than blocking, so short deadlines are safe.
    "get_task": RetryPolicy(deadline=30.0),
    # Batched leases share get_task's semantics: a replayed lease at worst
    # strands tasks in _doing for the watchdog to recover, same as today.
    "get_task_batch": RetryPolicy(deadline=30.0),
    "report_task_result": RetryPolicy(deadline=30.0),
    # Duplicate reports are absorbed server-side (unknown/duplicate ids are
    # acknowledged and discarded), so the batch report retries like the
    # single-task one.
    "report_task_results": RetryPolicy(deadline=30.0),
    "report_evaluation_metrics": RetryPolicy(deadline=60.0),
    "report_version": RetryPolicy(deadline=30.0),
    "get_comm_rank": RetryPolicy(deadline=30.0),
    "lease_steps": RetryPolicy(deadline=30.0),
    "report_lease": RetryPolicy(deadline=30.0),
    "report_worker_liveness": RetryPolicy(deadline=30.0),
    "get_job_status": RetryPolicy(deadline=15.0),
    # Hint polls are periodic and read-only; a missed poll self-heals on
    # the next interval, so don't burn retry budget.
    "get_world_hint": RetryPolicy(deadline=10.0, max_attempts=2),
    # Telemetry pushes are periodic and self-healing (a lost snapshot is
    # resent as a full resync on the next interval), so a failed push is
    # never worth burning retry budget on: one connectivity retry, and a
    # timed-out push — which may have applied and bumped the seq server
    # side — must NOT replay (the replayed seq would read as a gap and
    # force a spurious full resync).
    "report_telemetry": RetryPolicy(
        deadline=15.0,
        max_attempts=2,
        retryable_codes=_RETRYABLE_CONNECTIVITY,
    ),
    # Profile fan-out blocks for the capture duration on every role; not
    # idempotent (each attempt burns a capture slot on every endpoint),
    # so a timed-out request is never replayed and connectivity failures
    # retry once.
    "start_profile": RetryPolicy(
        deadline=120.0,
        max_attempts=2,
        retryable_codes=_RETRYABLE_CONNECTIVITY,
    ),
    # Pserver service: payload-bearing; pushes that time out may have
    # applied, so only UNAVAILABLE replays them.
    "push_model": RetryPolicy(deadline=120.0),
    "push_embedding_table_infos": RetryPolicy(deadline=60.0),
    "pull_dense_parameters": RetryPolicy(deadline=60.0),
    "pull_embedding_vectors": RetryPolicy(deadline=60.0),
    "pull_embedding_table": RetryPolicy(deadline=120.0),
    "push_gradients": RetryPolicy(
        deadline=60.0, retryable_codes=_RETRYABLE_CONNECTIVITY
    ),
    # Same non-idempotence as push_gradients (a timed-out chunk may have
    # landed and counted toward the reassembly), with the same deadline:
    # chunking means each sub-request is bounded by THIS deadline instead
    # of one giant push needing a one-off larger budget.
    "push_gradients_packed": RetryPolicy(
        deadline=60.0, retryable_codes=_RETRYABLE_CONNECTIVITY
    ),
    # Collective service: a full model state pull during elastic regroup.
    # Deadline NOT retried: rejoin latency is the product being measured
    # there — a wedged rank-0 must surface after one budget, not five
    # (broadcast.pull_state shares one budget between probe and RPC).
    "pull_model": RetryPolicy(
        deadline=120.0, retryable_codes=_RETRYABLE_CONNECTIVITY
    ),
}

# Environment overrides (read once; reload_config() re-reads — used by tests
# and by drills that shrink deadlines to force retries):
#   ELASTICDL_RPC_DEADLINES        JSON {method: seconds}
#   ELASTICDL_RPC_MAX_ATTEMPTS     int, all methods
#   ELASTICDL_RPC_BACKOFF_BASE     float, all methods
#   ELASTICDL_RPC_BACKOFF_MAX     float, all methods
#   ELASTICDL_RPC_BREAKER_THRESHOLD  int (<=0 disables the breaker)
#   ELASTICDL_RPC_BREAKER_COOLDOWN   float seconds
#   ELASTICDL_RPC_READY_TIMEOUT      float seconds (0 disables ready-wait)
_config_lock = threading.Lock()
_policy_cache = None


def _load_policies():
    policies = dict(METHOD_POLICIES)
    overrides = {}
    raw = knobs.raw("ELASTICDL_RPC_DEADLINES")
    if raw:
        try:
            overrides = {
                str(k): float(v) for k, v in json.loads(raw).items()
            }
        except (ValueError, AttributeError):
            logger.warning("Bad ELASTICDL_RPC_DEADLINES %r; ignored", raw)
    changes = {}
    for env, field, cast in (
        ("ELASTICDL_RPC_MAX_ATTEMPTS", "max_attempts", int),
        ("ELASTICDL_RPC_BACKOFF_BASE", "backoff_base", float),
        ("ELASTICDL_RPC_BACKOFF_MAX", "backoff_max", float),
    ):
        raw = knobs.raw(env)
        if raw:
            try:
                changes[field] = cast(raw)
            except ValueError:
                logger.warning("Bad %s %r; ignored", env, raw)
    for method, policy in list(policies.items()):
        per = dict(changes)
        if method in overrides:
            per["deadline"] = overrides[method]
        if per:
            policies[method] = dataclasses.replace(policy, **per)
    return policies


def policy_for(method):
    """RetryPolicy for a full ("/pkg.Service/name") or short method name."""
    global _policy_cache
    with _config_lock:
        if _policy_cache is None:
            _policy_cache = _load_policies()
        return _policy_cache.get(
            method.rsplit("/", 1)[-1], RetryPolicy()
        )


def reload_config():
    """Re-read env overrides (tests / in-process drills). Live channels
    hold references to their peer's breaker, so breakers are re-tuned and
    reset IN PLACE — clearing the registry would split per-peer state
    between old channels and new ones."""
    global _policy_cache
    with _config_lock:
        _policy_cache = None
    threshold = knobs.get_int("ELASTICDL_RPC_BREAKER_THRESHOLD")
    cooldown = knobs.get_float("ELASTICDL_RPC_BREAKER_COOLDOWN")
    with _breakers_lock:
        for breaker in _breakers.values():
            with breaker._lock:
                breaker.threshold = threshold
                breaker.cooldown = cooldown
                breaker._state = CircuitBreaker.CLOSED
                breaker._failures = 0
                breaker._probing = False


def ready_timeout():
    """The channel-readiness probe budget (seconds) this process uses —
    the single accessor for ELASTICDL_RPC_READY_TIMEOUT, shared by
    build_channel and clients that probe on their own (PSClient)."""
    return knobs.get_float("ELASTICDL_RPC_READY_TIMEOUT")


# build_channel's `ready_timeout` PARAMETER shadows the accessor above;
# this alias keeps the accessor the single reader of the knob there.
_default_ready_timeout = ready_timeout


# ---------- synthetic call objects ----------


class SyntheticRpcError(grpc.RpcError, grpc.Call, grpc.Future):
    """A locally-manufactured failed call: raised by the circuit breaker's
    fast-fail path and by client-side chaos injection. Implements the
    Call/Future surface so it can stand in anywhere a real failed call
    object can."""

    def __init__(self, code, details):
        super().__init__()
        self._code = code
        self._details = details

    # grpc.Call
    def initial_metadata(self):
        return ()

    def trailing_metadata(self):
        return ()

    def code(self):
        return self._code

    def details(self):
        return self._details

    def is_active(self):
        return False

    def time_remaining(self):
        return 0.0

    def add_callback(self, callback):
        return False

    # grpc.Future
    def cancel(self):
        return False

    def cancelled(self):
        return False

    def running(self):
        return False

    def done(self):
        return True

    def result(self, timeout=None):
        raise self

    def exception(self, timeout=None):
        return self

    def traceback(self, timeout=None):
        return None

    def add_done_callback(self, fn):
        fn(self)

    def __str__(self):
        return f"SyntheticRpcError({self._code}, {self._details!r})"


class CircuitOpenError(SyntheticRpcError):
    def __init__(self, peer, method):
        super().__init__(
            grpc.StatusCode.UNAVAILABLE,
            f"circuit breaker open for peer {peer} (method {method})",
        )
        self.peer = peer


# ---------- circuit breaker ----------


class CircuitBreaker:
    """Per-peer consecutive-failure breaker.

    closed --(threshold consecutive connectivity failures)--> open
    open   --(cooldown elapsed)--> half-open (one probe admitted)
    half-open --probe success--> closed; --probe failure--> open again
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, peer, threshold=None, cooldown=None):
        self.peer = peer
        self.threshold = (
            threshold
            if threshold is not None
            else knobs.get_int("ELASTICDL_RPC_BREAKER_THRESHOLD")
        )
        self.cooldown = (
            cooldown
            if cooldown is not None
            else knobs.get_float("ELASTICDL_RPC_BREAKER_COOLDOWN")
        )
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        self._probe_started = 0.0

    @property
    def state(self):
        with self._lock:
            return self._state

    def allow(self):
        """May a call proceed right now? Transitions open -> half-open when
        the cooldown has elapsed; half-open admits exactly one probe."""
        if self.threshold <= 0:  # breaker disabled
            return True
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if time.time() - self._opened_at < self.cooldown:
                    return False
                self._state = self.HALF_OPEN
                self._probing = True
                self._probe_started = time.time()
                logger.info(
                    "Circuit for %s half-open; probing", self.peer
                )
                return True
            # HALF_OPEN: one probe in flight at a time — but a probe whose
            # outcome never reached record_* (caller crashed, outcome was
            # swallowed) must not wedge the breaker; re-admit after a
            # cooldown's worth of silence.
            if self._probing and (
                time.time() - self._probe_started < self.cooldown
            ):
                return False
            self._probing = True
            self._probe_started = time.time()
            return True

    def record_success(self):
        with self._lock:
            if self._state != self.CLOSED:
                logger.info("Circuit for %s closed again", self.peer)
            self._state = self.CLOSED
            self._failures = 0
            self._probing = False

    def record_failure(self):
        """One failed connectivity ATTEMPT (each retry counts — a dead
        peer whose every call burns 5 attempts trips after ~2 calls, which
        is the point: stop burning budgets fast. `threshold` is therefore
        consecutive failed attempts, not failed calls)."""
        if self.threshold <= 0:
            return
        with self._lock:
            self._failures += 1
            tripped = (
                self._state == self.HALF_OPEN
                or (
                    self._state == self.CLOSED
                    and self._failures >= self.threshold
                )
            )
            if tripped:
                self._state = self.OPEN
                self._opened_at = time.time()
                self._probing = False
                _TRIPS.labels(peer=self.peer).inc()
                logger.warning(
                    "Circuit for %s OPEN after %d consecutive failures "
                    "(cooldown %.1fs)",
                    self.peer,
                    self._failures,
                    self.cooldown,
                )


_breakers = {}
_breakers_lock = threading.Lock()


def breaker_for(peer):
    """The process-wide breaker for a peer address (shared by every channel
    to that peer, and consultable by clients e.g. PSClient degradation)."""
    with _breakers_lock:
        breaker = _breakers.get(peer)
        if breaker is None:
            breaker = CircuitBreaker(peer)
            _breakers[peer] = breaker
        return breaker


# ---------- retrying client interceptor ----------


class _CallDetails(grpc.ClientCallDetails):
    def __init__(self, base, timeout):
        self.method = base.method
        self.timeout = timeout
        self.metadata = base.metadata
        self.credentials = base.credentials
        self.wait_for_ready = getattr(base, "wait_for_ready", None)
        self.compression = getattr(base, "compression", None)


def _short(method):
    return method.rsplit("/", 1)[-1]


class _RetryingFuture:
    """Future returned for `stub.method.future(...)` calls: retries happen
    lazily inside result()/exception(), on the caller's thread, so a fan-out
    of N futures still overlaps its healthy peers while one retries.

    Contract caveat: done()/running()/cancel()/add_done_callback reflect
    the CURRENT attempt only — a first attempt that failed fast reads as
    done even though result() may still retry. In-repo callers harvest
    exclusively via result()/exception(); poll-style consumers should
    treat done() as advisory."""

    def __init__(self, interceptor, continuation, details, request, call,
                 policy, attempt):
        self._i = interceptor
        self._continuation = continuation
        self._details = details
        self._request = request
        self._call = call
        self._policy = policy
        self._attempt = attempt

    def result(self, timeout=None):
        while True:
            try:
                value = self._call.result(timeout)
            except grpc.RpcError as err:
                code = err.code() if hasattr(err, "code") else None
                retry = self._i.on_failure(
                    self._details, self._policy, code, self._attempt
                )
                if not retry:
                    raise
                self._attempt += 1
                self._call = self._i.reissue(
                    self._continuation, self._details, self._request
                )
                continue
            self._i.on_success(self._details)
            return value

    def exception(self, timeout=None):
        try:
            self.result(timeout)
            return None
        except grpc.RpcError as err:
            return err

    def done(self):
        return self._call.done()

    def running(self):
        return self._call.running()

    def cancelled(self):
        return self._call.cancelled()

    def cancel(self):
        return self._call.cancel()

    def code(self):
        return self._call.code()

    def details(self):
        return self._call.details()

    def add_done_callback(self, fn):
        self._call.add_done_callback(lambda _c: fn(self))

    def traceback(self, timeout=None):
        return self._call.traceback(timeout)


class RetryingClientInterceptor(grpc.UnaryUnaryClientInterceptor):
    """Outermost interceptor on every built channel: injects the
    per-method default deadline, classifies failures against the method's
    RetryPolicy, retries with jittered exponential backoff, and consults
    the peer's circuit breaker (fail-fast when open)."""

    def __init__(self, peer, rng=None):
        self._peer = peer
        self._breaker = breaker_for(peer)
        self._rng = rng if rng is not None else random.Random()
        self._rng_lock = threading.Lock()

    # -- shared retry machinery (used by the blocking path and the future
    # wrapper) --

    def on_success(self, details):
        self._breaker.record_success()

    def on_failure(self, details, policy, code, attempt):
        """Bookkeep one failed attempt; True when the caller should retry
        (after this method has slept the backoff)."""
        method = _short(details.method)
        connectivity = code in _RETRYABLE
        if connectivity:
            self._breaker.record_failure()
        elif code is not None:
            # A non-connectivity status (INVALID_ARGUMENT, INTERNAL, ...)
            # means the peer ANSWERED: connectivity-wise that's a success,
            # and it must release a half-open probe instead of wedging it.
            self._breaker.record_success()
        if (
            code is None
            or not policy.retryable(code)
            or attempt >= policy.max_attempts - 1
        ):
            _FAILURES.labels(
                method=method, code=getattr(code, "name", str(code))
            ).inc()
            return False
        if not self._breaker.allow():
            # Peer declared down mid-retry: stop burning the budget.
            _FAILURES.labels(method=method, code="BREAKER_OPEN").inc()
            return False
        _RETRIES.labels(method=method).inc()
        with self._rng_lock:
            delay = policy.backoff(attempt, self._rng)
        logger.debug(
            "Retrying %s to %s in %.2fs (attempt %d, %s)",
            method,
            self._peer,
            delay,
            attempt + 2,
            code,
        )
        time.sleep(delay)
        return True

    def reissue(self, continuation, details, request):
        try:
            return continuation(details, request)
        except grpc.RpcError as err:
            return err if _is_call(err) else _as_call(err)

    # -- interceptor entry point --

    def intercept_unary_unary(self, continuation, details, request):
        policy = policy_for(details.method)
        if details.timeout is None and policy.deadline > 0:
            details = _CallDetails(details, policy.deadline)
        if not self._breaker.allow():
            # RETURN the failed call rather than raising: grpc invokes
            # this interceptor synchronously even for `.future()` calls,
            # and a raise there would explode out of a fan-out's
            # future-creation loop (e.g. PSClient's per-shard
            # comprehensions) instead of reaching its per-future
            # mark-degraded handling. Blocking callers still see the
            # exception — the machinery calls result(), which raises it.
            _FAST_FAILS.labels(peer=self._peer).inc()
            return CircuitOpenError(self._peer, _short(details.method))
        call = self.reissue(continuation, details, request)
        if call.done():
            code = call.code()
            if code is None or code == grpc.StatusCode.OK:
                self.on_success(details)
                return call
        # Failed-or-in-flight first attempt: ALL retrying happens lazily
        # inside the wrapper's result(). Blocking callers reach it
        # immediately (the interceptor machinery calls result()); a
        # fan-out's future() calls return instantly even when the first
        # attempt already failed synchronously (client-side chaos, fast
        # connection refusal) — retrying inline here would serialize the
        # fan-out with this thread's backoff sleeps.
        return _RetryingFuture(
            self, continuation, details, request, call, policy, 0
        )


def _is_call(err):
    return hasattr(err, "done") and hasattr(err, "result")


def _as_call(err):
    code = err.code() if hasattr(err, "code") else grpc.StatusCode.UNKNOWN
    details = err.details() if hasattr(err, "details") else str(err)
    return SyntheticRpcError(code, details)


# ---------- stubs / servers / channels ----------


class Stub:
    """Client stub: one callable attribute per spec method."""

    def __init__(self, channel: grpc.Channel, spec: ServiceSpec):
        for method, (req_cls, resp_cls) in spec.methods.items():
            setattr(
                self,
                method,
                channel.unary_unary(
                    f"/{spec.name}/{method}",
                    # Duck-typed on purpose (not req_cls.SerializeToString):
                    # out-of-band requests (tensor_utils.PackedPushRequest)
                    # serialize themselves by joining the header with raw
                    # payload views instead of round-tripping the bytes
                    # through a proto message.
                    request_serializer=lambda m: m.SerializeToString(),
                    response_deserializer=resp_cls.FromString,
                ),
            )


def add_servicer_to_server(servicer, spec: ServiceSpec, server: grpc.Server):
    """Register servicer methods (matched by name) for the spec's service."""
    handlers = {}
    for method, (req_cls, resp_cls) in spec.methods.items():
        handlers[method] = grpc.unary_unary_rpc_method_handler(
            getattr(servicer, method),
            request_deserializer=req_cls.FromString,
            response_serializer=resp_cls.SerializeToString,
        )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(spec.name, handlers),)
    )


def _chaos_server_interceptors(chaos):
    if chaos is None:
        from elasticdl_tpu.chaos import injection

        chaos = injection.schedule_from_env()
    if chaos is None:
        return ()
    from elasticdl_tpu.chaos import injection

    return (injection.ChaosServerInterceptor(chaos),)


def build_server(max_workers: int = 64, chaos=None) -> grpc.Server:
    # The tracing interceptor propagates edl-trace-* metadata into each
    # handler's context and records server spans once a recorder is
    # configured (observability.setup); unconfigured it costs one dict
    # lookup per RPC. The chaos interceptor (configured runs only) sits
    # inside tracing so injected faults still show up in traces.
    return grpc.server(
        concurrent.futures.ThreadPoolExecutor(max_workers=max_workers),
        options=GRPC_CHANNEL_OPTIONS,
        interceptors=(
            tracing.TracingServerInterceptor(),
            *_chaos_server_interceptors(chaos),
        ),
    )


def serve(servicer, spec: ServiceSpec, port: int = 0, max_workers: int = 64,
          chaos=None):
    """Start a server for one servicer; returns (server, bound_port)."""
    server = build_server(max_workers, chaos=chaos)
    add_servicer_to_server(servicer, spec, server)
    bound = server.add_insecure_port(f"[::]:{port}")
    if bound == 0:
        raise RuntimeError(f"failed to bind gRPC server to port {port}")
    server.start()
    return server, bound


def wait_channel_ready(addr, timeout, abort_check=None):
    """TCP-probe `addr` until it accepts a connection or `timeout` elapses.
    Returns True when the peer accepted. abort_check() returning True ends
    the wait early (e.g. "the subprocess that should bind this port died")."""
    host, _, port = addr.rpartition(":")
    host = host or "127.0.0.1"
    try:
        port = int(port)
    except ValueError:
        return False
    deadline = time.time() + timeout
    while time.time() < deadline:
        if abort_check is not None and abort_check():
            return False
        try:
            probe = socket.create_connection((host, port), timeout=1)
            probe.close()
            return True
        except OSError:
            time.sleep(0.2)
    return False


def build_channel(addr: str, ready_timeout=None, chaos=None) -> grpc.Channel:
    """A hardened channel to `addr`: readiness-waited, then interceptor
    stack [retry/deadline/breaker -> tracing -> chaos? -> wire].

    ready_timeout: seconds to TCP-probe before opening (None reads
    ELASTICDL_RPC_READY_TIMEOUT via rpc.ready_timeout(), default 30; 0
    skips the probe). On probe timeout the channel is still built — the
    retry plane owns the failure from there."""
    if ready_timeout is None:
        # (the module-level ready_timeout() accessor; the parameter
        # shadows its name here)
        ready_timeout = _default_ready_timeout()
    if ready_timeout > 0:
        if not wait_channel_ready(addr, ready_timeout):
            logger.warning(
                "Peer %s not accepting connections after %.1fs; opening "
                "the channel anyway (retries/breaker take over)",
                addr,
                ready_timeout,
            )
    channel = grpc.insecure_channel(addr, options=GRPC_CHANNEL_OPTIONS)
    # grpc.intercept_channel invokes the FIRST listed interceptor first
    # (outermost). Order: retry (outermost, so every attempt re-runs the
    # inner stack) -> tracing (each attempt records its own client span,
    # and trace-context injection rides every retry so one task's RPC
    # chain shares a trace id across processes) -> chaos (innermost,
    # closest to the wire — injected faults look like the network).
    interceptors = [RetryingClientInterceptor(addr)]
    interceptors.append(tracing.TracingClientInterceptor())
    if chaos is None:
        from elasticdl_tpu.chaos import injection

        chaos = injection.schedule_from_env()
    if chaos is not None:
        from elasticdl_tpu.chaos import injection

        interceptors.append(injection.ChaosClientInterceptor(chaos))
    return grpc.intercept_channel(channel, *interceptors)
