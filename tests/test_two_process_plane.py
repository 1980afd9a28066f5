"""Two-process service plane: frontend → history → matching across a
REAL process boundary.

Reference: the defining topology of the reference — stateless frontends
routing to history hosts by shard and matching hosts by task list over
the ring + RPC (client/history/client.go:844-846, common/rpc.go:55-67).
Here: two OS processes share a sqlite store; each runs a HistoryService
owning the shards the ring assigns it plus a MatchingEngine, served
over gRPC (rpc/server.py). The parent's workflow lands on a
child-owned shard, so StartWorkflowExecution crosses the wire; the
child's transfer queue pushes the decision task to the PARENT's
matching engine (task list ring), crossing back; the parent polls and
completes the workflow.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

import pytest

from cadence_tpu.client import RoutedHistoryClient, RoutedMatchingClient
from cadence_tpu.cluster import ClusterMetadata
from cadence_tpu.frontend import AdminHandler, DomainHandler, WorkflowHandler
from cadence_tpu.matching import MatchingEngine
from cadence_tpu.matching.engine import PollRequest
from cadence_tpu.runtime.api import Decision, StartWorkflowRequest
from cadence_tpu.core.enums import DecisionType
from cadence_tpu.runtime.domains import DomainCache
from cadence_tpu.runtime.membership import Monitor
from cadence_tpu.runtime.persistence.sqlite import create_sqlite_bundle
from cadence_tpu.runtime.service import HistoryService
from cadence_tpu.rpc.server import HistoryRPCServer, MatchingRPCServer
from cadence_tpu.utils.hashing import shard_for_workflow

# 16 shards, not 4: the ring is seeded with real (random-port) host
# identities, and with only 4 shard keys there's a ~6% chance one host
# owns every shard, which starves the cross-process assertion below.
NUM_SHARDS = 16

CHILD_SCRIPT = r"""
import sys, time
db, my_h, my_m, peer_h, peer_m, ready = sys.argv[1:7]

from cadence_tpu.client import RoutedHistoryClient, RoutedMatchingClient
from cadence_tpu.runtime.domains import DomainCache
from cadence_tpu.runtime.membership import Monitor
from cadence_tpu.runtime.persistence.sqlite import create_sqlite_bundle
from cadence_tpu.runtime.service import HistoryService
from cadence_tpu.matching import MatchingEngine
from cadence_tpu.rpc.server import HistoryRPCServer, MatchingRPCServer

bundle = create_sqlite_bundle(db)
domains = DomainCache(bundle.metadata)
monitor = Monitor(self_identity=my_h)
monitor.resolver("history").set_hosts([peer_h, my_h])
monitor.resolver("matching").set_hosts([peer_m, my_m])
history = HistoryService(%(num_shards)d, bundle, domains, monitor)
hc = RoutedHistoryClient(monitor, history.controller)
matching = MatchingEngine(bundle.task, hc)
mc = RoutedMatchingClient(monitor, matching, local_identity=my_m)
history.wire(mc, hc)
history.start()
hs = HistoryRPCServer(history, address=my_h).start()
ms = MatchingRPCServer(matching, address=my_m).start()
with open(ready, "w") as f:
    f.write("ready")
while True:
    time.sleep(0.5)
""" % {"num_shards": NUM_SHARDS}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture()
def plane(tmp_path):
    db = str(tmp_path / "plane.db")
    my_h = f"127.0.0.1:{_free_port()}"
    my_m = f"127.0.0.1:{_free_port()}"
    child_h = f"127.0.0.1:{_free_port()}"
    child_m = f"127.0.0.1:{_free_port()}"
    ready = str(tmp_path / "ready")

    bundle = create_sqlite_bundle(db)
    domains = DomainCache(bundle.metadata)
    domain_handler = DomainHandler(bundle.metadata, ClusterMetadata())
    domain_handler.register_domain("tp-domain")
    domain_id = domains.get_domain_id("tp-domain")

    monitor = Monitor(self_identity=my_h)
    monitor.resolver("history").set_hosts([my_h, child_h])
    monitor.resolver("matching").set_hosts([my_m, child_m])
    history = HistoryService(NUM_SHARDS, bundle, domains, monitor)
    hc = RoutedHistoryClient(monitor, history.controller)
    matching = MatchingEngine(bundle.task, hc)
    mc = RoutedMatchingClient(monitor, matching, local_identity=my_m)
    history.wire(mc, hc)
    history.start()
    servers = [
        HistoryRPCServer(history, address=my_h).start(),
        MatchingRPCServer(matching, address=my_m).start(),
    ]
    frontend = WorkflowHandler(domain_handler, domains, hc, mc)

    script = tmp_path / "child.py"
    script.write_text(CHILD_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, str(script), db, child_h, child_m, my_h, my_m,
         ready],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    deadline = time.monotonic() + 60
    while not os.path.exists(ready):
        if child.poll() is not None:
            raise RuntimeError(
                f"child died: {child.stderr.read().decode()[-2000:]}"
            )
        if time.monotonic() > deadline:
            child.kill()
            raise RuntimeError("child never became ready")
        time.sleep(0.05)

    class Plane:
        pass

    p = Plane()
    p.frontend = p_frontend = frontend
    p.matching = matching
    p.monitor = monitor
    p.domain_id = domain_id
    p.my_h, p.my_m, p.child_h, p.child_m = my_h, my_m, child_h, child_m
    p.hc, p.mc = hc, mc
    p.child = child
    try:
        yield p
    finally:
        child.kill()
        child.wait(timeout=5)
        for s in servers:
            s.stop()
        history.stop()
        matching.shutdown()
        hc.close()
        mc.close()


def _pick(monitor, ring: str, owner: str, gen, n=2000):
    """Find a key the given host owns in the ring."""
    r = monitor.resolver(ring)
    for i in range(n):
        key = gen(i)
        if r.lookup(key).identity == owner:
            return key
    raise AssertionError(f"no key found owned by {owner}")


def test_cross_process_workflow_roundtrip(plane):
    # a workflow whose SHARD the child owns, on a task list whose
    # MATCHING host is the parent: Start crosses to the child's history
    # service; its transfer queue pushes the decision BACK to the
    # parent's matching engine; the parent polls and completes.
    # keys in the history ring are shard ids, not workflow ids
    r = plane.monitor.resolver("history")
    wf = next(
        f"wf-x-{i}" for i in range(5000)
        if r.lookup(
            str(shard_for_workflow(f"wf-x-{i}", NUM_SHARDS))
        ).identity == plane.child_h
    )
    tl = _pick(plane.monitor, "matching", plane.my_m,
               lambda i: f"tl-x-{i}")

    run_id = plane.frontend.start_workflow_execution(
        StartWorkflowRequest(
            domain="tp-domain", workflow_id=wf, workflow_type="echo",
            task_list=tl, execution_start_to_close_timeout_seconds=60,
        )
    )
    assert run_id

    # retry: under load a long poll can expire just as the task is
    # handed over (the decision then re-schedules via its timeout timer)
    task = None
    for _ in range(3):
        task = plane.frontend.poll_for_decision_task(
            "tp-domain", tl, identity="w", timeout_s=15.0
        )
        if task is not None:
            break
    assert task is not None, "decision task never crossed the plane"
    plane.frontend.respond_decision_task_completed(
        task.task_token,
        [Decision(DecisionType.CompleteWorkflowExecution,
                  {"result": b"done"})],
    )
    desc = plane.frontend.describe_workflow_execution("tp-domain", wf, run_id)
    assert not desc.is_running

    events, _ = plane.frontend.get_workflow_execution_history(
        "tp-domain", wf, run_id
    )
    assert events[0].event_type.name == "WorkflowExecutionStarted"
    assert events[-1].event_type.name == "WorkflowExecutionCompleted"


def test_remote_matching_poll(plane):
    """A task list owned by the CHILD: the parent's routed matching
    client polls across the process boundary."""
    wf = "wf-y-0"   # shard owner is irrelevant; the routed client finds it
    tl = _pick(plane.monitor, "matching", plane.child_m,
               lambda i: f"tl-y-{i}")
    run_id = plane.frontend.start_workflow_execution(
        StartWorkflowRequest(
            domain="tp-domain", workflow_id=wf, workflow_type="echo",
            task_list=tl, execution_start_to_close_timeout_seconds=60,
        )
    )
    assert run_id
    task = None
    for _ in range(3):
        task = plane.mc.poll_for_decision_task(
            PollRequest(domain_id=plane.domain_id, task_list=tl,
                        identity="w", timeout_s=15.0)
        )
        if task is not None:
            break
    assert task is not None, "remote matching poll returned nothing"


def test_shard_move_mid_traffic_converges(plane):
    """Kill the owning host mid-traffic (VERDICT r4 #4): the routed
    client must retry through ShardOwnershipLost/UNAVAILABLE, re-resolve
    the ring once the dead host is evicted, and converge on the new
    owner with NO error surfaced to the caller."""
    import threading

    from cadence_tpu.runtime.api import SignalRequest

    r = plane.monitor.resolver("history")
    wf = next(
        f"wf-m-{i}" for i in range(5000)
        if r.lookup(
            str(shard_for_workflow(f"wf-m-{i}", NUM_SHARDS))
        ).identity == plane.child_h
    )
    tl = _pick(plane.monitor, "matching", plane.my_m,
               lambda i: f"tl-m-{i}")
    run_id = plane.frontend.start_workflow_execution(
        StartWorkflowRequest(
            domain="tp-domain", workflow_id=wf, workflow_type="echo",
            task_list=tl, execution_start_to_close_timeout_seconds=60,
        )
    )
    assert run_id

    # the owner dies hard; nothing has updated the ring yet
    plane.child.kill()
    plane.child.wait(timeout=5)

    errors = []

    def _signal():
        try:
            plane.frontend.signal_workflow_execution(
                SignalRequest(domain="tp-domain", workflow_id=wf,
                              signal_name="mid-move", input=b"x")
            )
        except Exception as e:  # surfaced error = test failure
            errors.append(e)

    t = threading.Thread(target=_signal, daemon=True)
    t.start()
    # while the signal is retrying against the dead host, the ring is
    # updated (stand-in for the failure detector evicting the host);
    # the parent's controller rebalances and acquires the shard
    time.sleep(0.7)
    plane.monitor.resolver("history").set_hosts([plane.my_h])
    plane.monitor.resolver("matching").set_hosts([plane.my_m])
    t.join(timeout=15)
    assert not t.is_alive(), "signal never converged"
    assert not errors, f"caller saw {errors!r}"

    events, _ = plane.frontend.get_workflow_execution_history(
        "tp-domain", wf, run_id
    )
    names = [e.event_type.name for e in events]
    assert "WorkflowExecutionSignaled" in names, names


def test_dead_host_evicted_and_shards_reacquired_without_remove_host(plane):
    """VERDICT r4 #5: kill -9 the owning process and make NO manual ring
    update. The failure detector must notice within its probe budget,
    evict the host (firing rebalance), and a routed call issued against
    the dead owner must converge on the survivor with no error."""
    from cadence_tpu.rpc.client import grpc_ping
    from cadence_tpu.runtime.api import SignalRequest
    from cadence_tpu.runtime.membership import FailureDetector

    r = plane.monitor.resolver("history")
    wf = next(
        f"wf-fd-{i}" for i in range(5000)
        if r.lookup(
            str(shard_for_workflow(f"wf-fd-{i}", NUM_SHARDS))
        ).identity == plane.child_h
    )
    tl = _pick(plane.monitor, "matching", plane.my_m,
               lambda i: f"tl-fd-{i}")
    run_id = plane.frontend.start_workflow_execution(
        StartWorkflowRequest(
            domain="tp-domain", workflow_id=wf, workflow_type="echo",
            task_list=tl, execution_start_to_close_timeout_seconds=60,
        )
    )
    assert run_id

    det = FailureDetector(
        plane.monitor, grpc_ping,
        own_identities={plane.my_h, plane.my_m},
        services=["history", "matching"],
        probe_interval_s=0.2, failure_threshold=2,
    ).start()
    try:
        plane.child.kill()
        plane.child.wait(timeout=5)
        # no set_hosts/remove_host anywhere: the detector does it
        plane.frontend.signal_workflow_execution(
            SignalRequest(domain="tp-domain", workflow_id=wf,
                          signal_name="after-death", input=b"x")
        )
        members = [
            h.identity
            for h in plane.monitor.resolver("history").members()
        ]
        assert plane.child_h not in members, members
        events, _ = plane.frontend.get_workflow_execution_history(
            "tp-domain", wf, run_id
        )
        names = [e.event_type.name for e in events]
        assert "WorkflowExecutionSignaled" in names, names
    finally:
        det.stop()
