"""Tenant-fair serving: identity, weighted shares, and rate quotas.

The admission gate from PR 9 is honest under overload but
tenant-blind: one hot tenant's burst fills the whole in-flight budget
and every other tenant starves behind it. This module gives the
serving tier a tenant dimension without changing anything for
deployments that don't opt in:

- **Identity**: the `X-Tenant` request header names the tenant;
  absent/blank means the `"default"` tenant. With `--serve_tenants`
  unset there is NO policy object and the whole layer is inert —
  responses are byte-identical to a tenancy-free build (pinned in
  tests/test_tenancy.py).
- **Shares** (`--serve_tenants name=weight,...`): each configured
  tenant owns `weight / sum(active weights)` of the admission gate's
  in-flight budget (`--serve_queue_depth`). The bound is computed
  against *recently active* tenants only, so a lone tenant still uses
  the full queue (work conservation) while contending tenants converge
  to their weighted shares. Tenants not named in the spec collapse
  into one `"other"` bucket at `--serve_tenant_default_weight`.
- **Rate quotas** (`--serve_tenant_qps`): a deterministic token bucket
  per tenant; an over-quota request sheds as 503
  `shed_reason=tenant_quota` with `Retry-After` derived from THAT
  tenant's bucket refill time — never the fleet-wide queue estimate.
- **Batch fairness**: `dwrr_take` is the deficit-weighted-round-robin
  order the batcher uses to fill a device batch when multiple
  tenants are pending, so a full batch cannot be monopolized by one
  tenant's backlog.
- **Bounded metric cardinality**: every tenant-labeled metric
  registration funnels through `tenant_metric`, which refuses any
  label value outside the policy's closed set (configured tenants +
  `default` + `other`). The registration names here are mirrored in
  scripts/check_metrics_doc.py's `_DYNAMIC_REGISTRATIONS` allowlist —
  labels are the dynamic dimension, the name set stays closed.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from code2vec_tpu import obs

# The request header naming the tenant, parsed once at the server edge
# and forwarded verbatim by the fleet router and the supervisor proxy
# (serving/forwarding.py REQUEST_FORWARD_HEADERS).
TENANT_HEADER = "X-Tenant"
# Absent/blank header ⇒ this tenant. Always part of the label set.
DEFAULT_TENANT = "default"
# Metric label (and scheduling bucket) every UNCONFIGURED tenant
# collapses into: the label set stays closed no matter what clients
# send, so a header fuzzer cannot grow the registry.
OTHER_LABEL = "other"

# How long (seconds) a tenant stays in the "active" set after its last
# admission attempt. Share bounds divide the queue among active tenants
# only: a tenant idle longer than this stops reserving queue room
# (work conservation), while any tenant probing at >= 1/window Hz keeps
# its share reserved against a hot tenant's flood.
ACTIVE_WINDOW_S = 10.0


def parse_tenant_weights(spec) -> Dict[str, float]:
    """Parse `--serve_tenants` ("name=weight,name=weight,..."; a bare
    name means weight 1) into an ordered {name: weight} map. Raises
    ValueError on empty names, non-positive or unparsable weights, and
    duplicates — a typo'd share spec must fail at startup, not skew
    production fairness silently."""
    out: Dict[str, float] = {}
    for part in str(spec or "").replace(" ", "").split(","):
        if not part:
            continue
        name, sep, raw = part.partition("=")
        if not name:
            raise ValueError(
                f"--serve_tenants entry {part!r} has an empty tenant "
                f"name")
        if name in out:
            raise ValueError(
                f"--serve_tenants names tenant {name!r} twice")
        if sep:
            try:
                weight = float(raw)
            except ValueError:
                raise ValueError(
                    f"--serve_tenants weight for {name!r} must be a "
                    f"number, got {raw!r}")
        else:
            weight = 1.0
        if weight <= 0:
            raise ValueError(
                f"--serve_tenants weight for {name!r} must be > 0 "
                f"(got {weight:g}); use 0 qps, not 0 weight, to block "
                f"a tenant")
        out[name] = weight
    return out


def parse_tenant_qps(spec) -> Dict[str, float]:
    """Parse `--serve_tenant_qps`: either one bare number (the same
    quota for every tenant, `*` internally) or "name=qps,..." per
    tenant. 0 or unset = uncapped. Raises ValueError on negative or
    unparsable rates."""
    text = str(spec or "").replace(" ", "")
    if not text:
        return {}
    out: Dict[str, float] = {}
    for part in text.split(","):
        if not part:
            continue
        name, sep, raw = part.partition("=")
        if not sep:
            name, raw = "*", part
        if not name:
            raise ValueError(
                f"--serve_tenant_qps entry {part!r} has an empty "
                f"tenant name")
        if name in out:
            raise ValueError(
                f"--serve_tenant_qps names tenant {name!r} twice")
        try:
            qps = float(raw)
        except ValueError:
            raise ValueError(
                f"--serve_tenant_qps rate for {name!r} must be a "
                f"number, got {raw!r}")
        if qps < 0:
            raise ValueError(
                f"--serve_tenant_qps rate for {name!r} must be >= 0 "
                f"(0 = uncapped), got {qps:g}")
        out[name] = qps
    return out


class TokenBucket:
    """Deterministic token bucket: `rate_qps` tokens/s up to `burst`.
    The clock is injectable so refill behavior is testable to the
    token — the fairness-law tests advance a fake clock and assert
    exact admit/refuse sequences."""

    def __init__(self, rate_qps: float, burst: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.rate = float(rate_qps)
        # default burst: one second's worth of quota, at least 1 token
        self.burst = float(burst) if burst is not None \
            else max(1.0, self.rate)
        self._clock = clock
        self._tokens = self.burst
        self._t_last = clock()
        self._lock = threading.Lock()

    def _refill_locked(self) -> None:
        now = self._clock()
        if now > self._t_last:
            self._tokens = min(
                self.burst,
                self._tokens + (now - self._t_last) * self.rate)
        self._t_last = now

    def try_take(self) -> bool:
        with self._lock:
            self._refill_locked()
            if self._tokens >= 1.0:
                self._tokens -= 1.0
                return True
            return False

    def retry_after_s(self) -> float:
        """Seconds until this bucket holds a whole token again — the
        per-tenant Retry-After base for a tenant_quota shed (the
        server adds jitter on top, as for every shed)."""
        with self._lock:
            self._refill_locked()
            if self._tokens >= 1.0:
                return 0.0
            if self.rate <= 0:
                # a zero-rate bucket never refills: the tenant is
                # administratively blocked; tell it to back off hard
                return 60.0
            return (1.0 - self._tokens) / self.rate


class TenantPolicy:
    """Parsed tenancy configuration: weighted shares, per-tenant rate
    quotas, and the CLOSED metric-label set. One instance per server,
    shared by the admission controller and the batcher. `None` (no
    `--serve_tenants`) means the layer is off end to end."""

    def __init__(self, weights: Dict[str, float],
                 default_weight: float = 1.0,
                 qps: Optional[Dict[str, float]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 active_window_s: float = ACTIVE_WINDOW_S):
        if not weights:
            raise ValueError("TenantPolicy needs at least one "
                             "configured tenant (use None for no "
                             "tenancy)")
        self.weights = dict(weights)
        self.default_weight = float(default_weight)
        if self.default_weight <= 0:
            raise ValueError("--serve_tenant_default_weight must be "
                             "> 0")
        self.qps = dict(qps or {})
        self.clock = clock
        self.active_window_s = float(active_window_s)
        # The closed label set: configured tenants + the default tenant
        # + the collapse bucket. This IS the cardinality bound — every
        # tenant-labeled registration is checked against it.
        self.labels: Tuple[str, ...] = tuple(dict.fromkeys(
            list(self.weights) + [DEFAULT_TENANT, OTHER_LABEL]))
        self._buckets: Dict[str, Optional[TokenBucket]] = {}
        self._bucket_lock = threading.Lock()

    @classmethod
    def from_config(cls, config,
                    clock: Callable[[], float] = time.monotonic
                    ) -> Optional["TenantPolicy"]:
        """Policy from --serve_tenants / --serve_tenant_default_weight
        / --serve_tenant_qps; None when --serve_tenants is unset (the
        zero-behavior-change contract)."""
        weights = parse_tenant_weights(
            getattr(config, "serve_tenants", ""))
        if not weights:
            return None
        return cls(
            weights,
            default_weight=float(getattr(
                config, "serve_tenant_default_weight", 1.0)),
            qps=parse_tenant_qps(
                getattr(config, "serve_tenant_qps", "")),
            clock=clock)

    # ---------------------------------------------------- identity

    @staticmethod
    def resolve(header_value: Optional[str]) -> str:
        """Raw tenant id from the X-Tenant header: stripped, blank or
        absent ⇒ the default tenant. This is the value recorded in
        trace attrs and flight-recorder entries (bounded rings, full
        fidelity); scheduling and metrics use `label()`."""
        tenant = (header_value or "").strip()
        return tenant or DEFAULT_TENANT

    def label(self, tenant: Optional[str]) -> str:
        """Collapse a raw tenant id onto the closed label set: a
        configured tenant keeps its name, `default` stays `default`,
        everything else becomes `other`."""
        tenant = self.resolve(tenant)
        if tenant in self.weights or tenant == DEFAULT_TENANT:
            return tenant
        return OTHER_LABEL

    def weight(self, label: Optional[str]) -> float:
        """Fair-share weight of a (collapsed) label; unconfigured
        labels (`default`, `other`) ride at the default weight."""
        if label is None:
            return self.default_weight
        return self.weights.get(label, self.default_weight)

    def bucket(self, label: str) -> Optional[TokenBucket]:
        """The label's rate-quota bucket; None = uncapped. Buckets are
        created once per label and shared across requests — `other` is
        ONE bucket for all unconfigured tenants together, matching its
        one metric label and one scheduling share."""
        with self._bucket_lock:
            if label not in self._buckets:
                qps = self.qps.get(label, self.qps.get("*", 0.0))
                self._buckets[label] = (
                    TokenBucket(qps, clock=self.clock) if qps > 0
                    else None)
            return self._buckets[label]

    def healthz(self) -> dict:
        return {
            "tenants": {name: {"weight": w,
                               "qps": self.qps.get(
                                   name, self.qps.get("*", 0.0))}
                        for name, w in self.weights.items()},
            "default_weight": self.default_weight,
            "labels": list(self.labels),
        }


# ------------------------------------------------------------ metrics

# The ONLY metric families that may carry a tenant label, mirrored in
# scripts/check_metrics_doc.py _DYNAMIC_REGISTRATIONS (the doc gate
# fails if this module registers a name outside that closed allowlist).
# Help strings match the literal registrations in server.py/admission.py
# so the registry's idempotent _get() sees one family either way.
_TENANT_METRICS = ("serving_requests_total",
                   "serving_requests_shed_total",
                   "serving_request_seconds")


def tenant_metric(kind: str, name: str, help_text: str, tenant: str,
                  allowed: Sequence[str], **labels):
    """The guarded funnel for every tenant-labeled registration:
    refuses a metric name outside the closed `_TENANT_METRICS` set and
    a tenant label value outside the policy's closed label set, so the
    registry can never grow unbounded tenant cardinality — a client
    fuzzing X-Tenant values hits `TenantPolicy.label()`'s collapse
    first and this assertion second."""
    if name not in _TENANT_METRICS:
        raise ValueError(
            f"{name!r} is not a tenant-labeled metric family "
            f"(allowed: {', '.join(_TENANT_METRICS)})")
    if tenant not in allowed:
        raise ValueError(
            f"tenant label {tenant!r} is outside the configured label "
            f"set {tuple(allowed)!r}; collapse it with "
            f"TenantPolicy.label() first (bounded-cardinality guard)")
    if kind == "counter":
        return obs.counter(name, help_text, tenant=tenant, **labels)
    if kind == "histogram":
        return obs.histogram(name, help_text, tenant=tenant, **labels)
    raise ValueError(f"unknown tenant metric kind {kind!r}")


# --------------------------------------------------------------- DWRR

def dwrr_take(pending, max_rows: int,
              weight_of: Callable[[Optional[str]], float],
              state: dict) -> Optional[List[int]]:
    """Deficit-weighted-round-robin batch fill: pick indices into
    `pending` (objects with `.tenant` and `.lines`) totalling at most
    `max_rows` rows, interleaving tenants by weighted deficit, FIFO
    within a tenant. Returns None when at most one tenant is pending —
    the caller keeps its plain FIFO path, byte-identical to the
    tenancy-free batcher for a single tenant.

    `state` persists across calls: {"deficits": {label: rows},
    "last": label} — a tenant's unused credit carries to the next
    batch, its deficit resets when its queue empties (classic DRR),
    and rotation resumes after the last-served tenant so the
    first-listed tenant holds no permanent head-of-batch advantage."""
    queues: Dict[Optional[str], List[int]] = {}
    for i, item in enumerate(pending):
        queues.setdefault(item.tenant, []).append(i)
    if len(queues) <= 1:
        return None
    deficits = state.setdefault("deficits", {})
    labels = sorted(queues, key=lambda t: (t is None, t))
    last = state.get("last")
    if last in labels:
        k = labels.index(last) + 1
        labels = labels[k:] + labels[:k]
    total_w = sum(weight_of(t) for t in labels) or 1.0
    taken: List[int] = []
    rows = 0
    while rows < max_rows:
        # can any nonempty queue's head still fit the batch?
        if taken and not any(
                q and rows + len(pending[q[0]].lines) <= max_rows
                for q in queues.values()):
            break
        progressed = False
        for t in labels:
            q = queues[t]
            if not q:
                deficits.pop(t, None)
                continue
            # quantum: this tenant's weighted slice of one full batch
            deficits[t] = deficits.get(t, 0.0) \
                + max_rows * weight_of(t) / total_w
            while q and rows < max_rows:
                n = len(pending[q[0]].lines)
                if taken and rows + n > max_rows:
                    break
                if deficits[t] < n and taken:
                    break
                taken.append(q.pop(0))
                deficits[t] -= n
                rows += n
                state["last"] = t
                progressed = True
            if not q:
                deficits.pop(t, None)
            if rows >= max_rows:
                break
        if not progressed:
            break
    return taken
