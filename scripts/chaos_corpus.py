"""Chaos soak: run the bench corpus under a randomized fault schedule.

Manual driver (not CI — the deterministic tier-1 chaos tests live in
tests/test_faults.py and tests/test_checkpoint.py).  Each round
analyzes the embedded corpus with a randomly drawn fault armed on the
resilience plane mid-run, then checks the two ladder invariants:

- findings identical to the fault-free reference run;
- the matching degradation counter moved.

Usage:
    JAX_PLATFORMS=cpu python scripts/chaos_corpus.py [--rounds N] [--seed S]

``--kill-resume`` instead drives the checkpoint/resume plane: for each
named injection point the chaos-tree analysis runs in a subprocess that
is SIGKILLed the moment the point is hit (``MYTHRIL_TPU_KILL_AT``,
journaling under a fresh ``--checkpoint-dir`` at every scheduler
round), then a second subprocess resumes from the journal, and the
round passes only when the resumed findings are identical to the
uninterrupted reference run.  A final round arms a lane-dependent
``lane_poison`` fault (no kill) and asserts the poisoned lane is
quarantined alone: ``quarantined_lanes`` >= 1 with ``demotions``
unchanged at 0, the context still on device.

``--fleet`` soaks the frontier fleet (mythril_tpu/parallel/fleet.py):
the chaos-tree workload runs under ``--workers 2`` while workers are
SIGKILLed at their transaction-boundary fault point (``worker_kill``,
first boundary and mid-corpus), heartbeats are partitioned away
(``lease_partition`` — the re-lease + zombie + stale-epoch-gossip
path, asserting ``gossip_dropped_stale`` >= 1 with verdicts
unchanged), gossip messages are dropped (``gossip_drop``), and the
kill switch (``MYTHRIL_TPU_FLEET=0``) is pinned to reproduce the exact
single-process pipeline — every round asserts findings identical to
the ``--workers 0`` reference, and the preemption rounds assert
``worker_deaths`` >= 1 (a round that kills nobody proved nothing).

``--serve`` soaks the persistent daemon instead: a real ``myth serve``
subprocess is driven over HTTP through five scenarios — (1) findings
parity vs in-process CLI runs while ``MYTHRIL_TPU_FAULT`` injection is
armed in the server, (2) SIGKILL + restart with readiness and parity
re-asserted, (3) per-source circuit breaker trip (via injected
``serve_crash`` request failures) and post-cooldown recovery, (4) a
tiny per-request deadline yielding a partial report with the next
request unaffected, and (5) queue-overflow shedding (depth cap 1) with
``Retry-After`` and no server death.  Each scenario runs a fresh server
subprocess with scenario-specific env; exit status is nonzero when any
scenario failed.

``--multihost`` soaks the serving fabric (serve/fabric.py): one
``myth serve --fleet-listen`` endpoint on a non-loopback interface
fronting two authenticated ``myth worker --connect`` processes.  The
corpus must answer with findings parity THROUGH the fabric (``mode:
fabric``, routed >= 1), a worker SIGKILL mid-request must be invisible
to the HTTP client (re-lease from the boundary journal), a hostile
unauthenticated peer must bounce off the handshake while service
continues, a coordinator SIGKILL+restart must be healed by the
workers' ``--reconnect`` redial, and ``MYTHRIL_TPU_FLEET=0`` must
yield the exact single-process serve path.

``--wild`` soaks the wild-bytecode envelope (disassembler triage +
resource governor + RPC provider pool): a flapping provider mid-load
must rotate through the pool to the exact triage verdict of a calm
load, a SIGKILL mid-corpus-sweep must be healed by ``--resume`` from
the fsynced journal (same contract count, zero crash verdicts), and a
governor breach on the state-heavy fixture must yield a ``partial``
verdict whose findings are a SUBSET of the unbudgeted run — degraded
analysis may miss findings, never invent them.

Exit status is nonzero when any round broke findings parity, so the
script doubles as a soak gate before hardware rounds.
"""

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# fault -> (arm kwargs, env overrides, args overrides for the round)
SCHEDULE = {
    "dispatch_hang": (
        {"times": 99, "hang_s": 1.0},
        {"MYTHRIL_TPU_DISPATCH_TIMEOUT": "0.4"},
        {},
    ),
    "dispatch_error": ({"times": 99}, {}, {}),
    "dispatch_garbage": ({"times": 99}, {}, {}),
    # the event-driven frontier rounds (ops/frontier.py) are their own
    # dispatch shape with their own watchdog keys — stall them
    # repeatedly and the retry/bisect/demote ladder must still land
    # identical findings
    "frontier_stall": ({"times": 99}, {}, {}),
    "probe_flap": ({"times": 1, "skip": 1}, {}, {}),
    "cdcl_error": ({"times": 1}, {}, {}),
    # prefetch only launches when the profit gate declines a frontier,
    # so this round must not force dispatch
    "prefetch_error": ({"times": 99}, {}, {"device_force_dispatch": False}),
    # the veritesting merge commit aborts at its fault seam
    # (laser/ethereum/veritest.py maybe_abort_merge): every abort must
    # degrade to plain forking — more states, identical findings — so
    # the round pins the tier ON and asserts corpus parity like the
    # rest of the ladder
    "merge_abort": ({"times": 99}, {"MYTHRIL_TPU_VERITEST": "1"}, {}),
}


def _analyze_corpus():
    """One pass over the embedded corpus plus the wide-frontier chaos
    tree (the contract whose dispatches the faults actually hit);
    returns {name: found_swcs} plus the summed resilience counters."""
    import bench
    from mythril_tpu.ops.batched_sat import dispatch_stats
    from mythril_tpu.resilience.telemetry import resilience_stats

    cases = bench._corpus() + [
        ("chaos_tree", bench.chaos_tree_contract(), 1, {"106"})
    ]
    results = {}
    counters = dict.fromkeys(resilience_stats.as_dict(), 0)
    for name, code, tx_count, _expected in cases:
        found, row = bench._analyze_one(
            name, code, tx_count, execution_timeout=120, max_depth=128
        )
        results[name] = sorted(found)
        for key in counters:
            counters[key] += row.get(key, 0)
    dispatch_stats.reset()
    return results, counters


# kill-resume schedule: (injection point, clean hits let through before
# the SIGKILL) — early and mid-analysis seams of every point the
# chaos-tree workload actually reaches (a point that is never hit makes
# its round vacuous, which the driver reports as a failure)
KILL_POINTS = [
    ("dispatch_hang", 0),    # first device dispatch (often pre-boundary)
    ("dispatch_hang", 4),    # a dispatch mid-analysis
    ("dispatch_garbage", 1), # after a dispatch returned (the point is
    #                          hit once per dispatch, and the chaos
    #                          tree makes two)
    ("cdcl_error", 0),       # first native CDCL call
    ("cdcl_error", 25),      # deep in the CDCL tail
    ("probe_flap", 1),       # a device health check mid-run
]

KR_TX_COUNT = 2  # two transactions => at least one mid-run boundary


def _kr_configure():
    """Child/process-local knobs shared by every kill-resume analysis
    (mirrors the soak configuration above: the workload must actually
    reach the device paths the kill points live on)."""
    import logging

    logging.basicConfig(level=logging.ERROR)
    from mythril_tpu.support.support_args import args

    args.device_min_lanes = 2
    args.device_force_dispatch = True
    args.word_probing = False
    args.async_dispatch = False  # dispatches stay on the kill path
    os.environ.setdefault("MYTHRIL_TPU_PALLAS", "off")  # CPU: gather path


def _kr_child(checkpoint_dir, resume) -> int:
    """Subprocess body: one chaos-tree analysis, journaling under
    ``checkpoint_dir`` (and resuming from it when ``resume``); prints
    one JSON line with the findings and the resilience counters.  The
    kill variant never reaches the print — MYTHRIL_TPU_KILL_AT lands
    first."""
    _kr_configure()
    import bench
    from mythril_tpu.support.support_args import args

    args.checkpoint_dir = checkpoint_dir
    args.resume_from = checkpoint_dir if resume else None
    found, row = bench._analyze_one(
        "chaos_tree", bench.chaos_tree_contract(), KR_TX_COUNT,
        execution_timeout=120, max_depth=128,
    )
    print(json.dumps({
        "found": sorted(found),
        "resumes": row.get("resumes", 0),
        "checkpoints_written": row.get("checkpoints_written", 0),
        "quarantined_lanes": row.get("quarantined_lanes", 0),
        "bisect_dispatches": row.get("bisect_dispatches", 0),
        "demotions": row.get("demotions", 0),
        "dispatches": row.get("dispatches", 0),
        "fused": row.get("fused", False),
    }))
    return 0


def _kr_spawn(checkpoint_dir=None, resume=False, extra_env=None):
    """Run one child analysis; returns (returncode, payload|None)."""
    env = dict(os.environ)
    env.pop("MYTHRIL_TPU_KILL_AT", None)
    env.pop("MYTHRIL_TPU_FAULT", None)
    env["MYTHRIL_TPU_CHECKPOINT_PERIOD"] = "0"  # refresh every round
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.abspath(__file__), "--kr-child"]
    if checkpoint_dir:
        cmd += ["--kr-dir", checkpoint_dir]
    if resume:
        cmd += ["--kr-resume"]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, env=env,
    )
    payload = None
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            payload = json.loads(line)
            break
    return proc.returncode, payload


def kill_resume_main() -> int:
    """The --kill-resume driver: SIGKILL at every seam, resume, demand
    identical findings; then the lane-poison quarantine round."""
    failures = []
    print("kill-resume: uninterrupted reference pass ...", file=sys.stderr)
    rc, reference = _kr_spawn()
    if rc != 0 or reference is None:
        print(json.dumps({"error": f"reference child exited {rc}"}))
        return 1
    print(json.dumps({"reference": reference}), file=sys.stderr)

    for point, skip in KILL_POINTS:
        with tempfile.TemporaryDirectory(prefix="mtpu-ckpt-") as ckpt:
            began = time.time()
            rc, _ = _kr_spawn(
                checkpoint_dir=ckpt,
                extra_env={"MYTHRIL_TPU_KILL_AT": f"{point}:{skip}"},
            )
            killed = rc == -9
            if not killed:
                # the child survived: the point was never hit, so the
                # round proved nothing — loud failure, not a pass
                failures.append({"point": point, "skip": skip,
                                 "error": f"never reached (exit {rc})"})
                print(json.dumps({"point": point, "skip": skip,
                                  "killed": False}))
                continue
            rc, resumed = _kr_spawn(checkpoint_dir=ckpt, resume=True)
            parity = (
                rc == 0 and resumed is not None
                and resumed["found"] == reference["found"]
            )
            row = {
                "point": point, "skip": skip, "killed": True,
                "wall_s": round(time.time() - began, 1),
                "findings_parity": parity,
                "resumes": resumed.get("resumes") if resumed else None,
                "checkpoints_written": (
                    resumed.get("checkpoints_written") if resumed else None
                ),
            }
            print(json.dumps(row))
            if not parity:
                failures.append({
                    "point": point, "skip": skip,
                    "found": resumed and resumed.get("found"),
                    "reference": reference["found"], "exit": rc,
                })

    # poisoned-lane quarantine: a repeatably failing lane must go to
    # the CDCL tail ALONE — context on device, no context demotion
    began = time.time()
    rc, poisoned = _kr_spawn(
        extra_env={"MYTHRIL_TPU_FAULT": "lane_poison:99:0:2"},
    )
    quarantine_ok = (
        rc == 0 and poisoned is not None
        and poisoned["found"] == reference["found"]
        and poisoned["quarantined_lanes"] >= 1
        and poisoned["demotions"] == reference["demotions"]
        and not poisoned["fused"]
    )
    print(json.dumps({
        "point": "lane_poison", "wall_s": round(time.time() - began, 1),
        "quarantine_ok": quarantine_ok,
        "quarantined_lanes": poisoned and poisoned.get("quarantined_lanes"),
        "bisect_dispatches": poisoned and poisoned.get("bisect_dispatches"),
        "demotions": poisoned and poisoned.get("demotions"),
    }))
    if not quarantine_ok:
        failures.append({"point": "lane_poison", "result": poisoned,
                         "exit": rc})

    if failures:
        print(json.dumps({"kill_resume_failures": failures}))
        return 1
    print(json.dumps({"kill_resume_ok": True,
                      "rounds": len(KILL_POINTS) + 1}))
    return 0


# ---------------------------------------------------------------------------
# --serve: soak the persistent daemon
# ---------------------------------------------------------------------------

SERVE_READY_TIMEOUT_S = 120.0


def _free_port() -> int:
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _http(method, url, payload=None, timeout=240):
    """(status, parsed-json-or-None, headers) without raising on 4xx/5xx."""
    import urllib.error
    import urllib.request

    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read() or b"null"), resp.headers
    except urllib.error.HTTPError as e:
        try:
            body = json.loads(e.read() or b"null")
        except ValueError:
            body = None
        return e.code, body, e.headers
    except Exception as e:  # noqa: BLE001 — connection refused etc.
        return 0, {"transport_error": str(e)}, {}


class _ServeChild:
    """One ``myth serve`` subprocess on an ephemeral port."""

    def __init__(self, extra_env=None, extra_args=None, port=None):
        self.port = port or _free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        env = dict(os.environ)
        env.pop("MYTHRIL_TPU_FAULT", None)
        env.pop("MYTHRIL_TPU_KILL_AT", None)
        env.update(extra_env or {})
        myth = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "myth",
        )
        self.proc = subprocess.Popen(
            [sys.executable, myth, "serve", "--port", str(self.port)]
            + list(extra_args or ()),
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    def wait_ready(self, timeout_s=SERVE_READY_TIMEOUT_S) -> bool:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if self.proc.poll() is not None:
                return False
            status, body, _ = _http("GET", self.base + "/readyz",
                                    timeout=5)
            if status == 200 and body and body.get("ready"):
                return True
            time.sleep(0.5)
        return False

    def analyze(self, payload, timeout=240):
        return _http("POST", self.base + "/analyze", payload,
                     timeout=timeout)

    def sigkill(self):
        self.proc.kill()
        self.proc.wait(timeout=30)

    def stop(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _serve_reference():
    """CLI-parity oracle: the embedded corpus analyzed in-process with
    the canonical per-contract reset sequence (what `myth analyze`
    does), keyed by contract name."""
    import bench

    reference = {}
    for name, code, tx_count, _expected in bench._corpus():
        found, _row = bench._analyze_one(
            name, code, tx_count, execution_timeout=120, max_depth=128
        )
        reference[name] = sorted(found)
    return reference


def serve_soak_main() -> int:
    """The --serve driver: overload, deadline, poison, kill — the
    daemon must shed, degrade, and recover; never die or change
    findings."""
    import bench

    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    print("serve soak: computing in-process CLI reference ...",
          file=sys.stderr)
    reference = _serve_reference()
    print(json.dumps({"reference": reference}), file=sys.stderr)
    corpus = {name: (code, tx) for name, code, tx, _ in bench._corpus()}

    # -- scenario 1: findings parity under armed fault injection
    # (cdcl_error:1, matching SCHEDULE above — the retry rung absorbs
    # one abort; more consecutive shots than retries would LEGITIMATELY
    # degrade verdicts to UNKNOWN)
    child = _ServeChild(extra_env={"MYTHRIL_TPU_FAULT": "cdcl_error:1"})
    try:
        check("faulted_server_ready", child.wait_ready())
        parity = {}
        for name, (code, tx_count) in corpus.items():
            status, body, _ = child.analyze({
                "code": code, "name": name, "tx_count": tx_count,
                "deadline_s": 240, "source": "soak",
            })
            parity[name] = (
                status == 200
                and body.get("findings_swc") == reference[name]
            )
        check("fault_injection_findings_parity", all(parity.values()),
              per_contract=parity)

        # -- scenario 2: SIGKILL the server, restart, stay ready -------
        child.sigkill()
        check("sigkill_delivered", True)
    finally:
        child.stop()
    child = _ServeChild()
    try:
        check("restart_after_sigkill_ready", child.wait_ready())
        name = "killbilly"
        code, tx_count = corpus[name]
        status, body, _ = child.analyze({
            "code": code, "name": name, "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })
        check(
            "restart_findings_parity",
            status == 200 and body.get("findings_swc") == reference[name],
            found=body.get("findings_swc") if body else None,
        )
    finally:
        child.stop()

    # -- scenario 3: breaker trips on poisoned requests, then recovers
    child = _ServeChild(extra_env={
        "MYTHRIL_TPU_FAULT": "serve_crash:2",
        "MYTHRIL_TPU_SERVE_BREAKER": "2",
        "MYTHRIL_TPU_SERVE_BREAKER_COOLDOWN": "1.0",
    })
    try:
        check("breaker_server_ready", child.wait_ready())
        code, tx_count = corpus["killbilly"]
        payload = {"code": code, "name": "killbilly",
                   "tx_count": tx_count, "source": "toxic"}
        crashes = [child.analyze(payload)[0] for _ in range(2)]
        status, body, headers = child.analyze(payload)
        tripped = (
            crashes == [500, 500]
            and status == 503
            and body and body["error"]["code"] == "breaker_open"
            and int(headers.get("Retry-After", 0)) >= 1
        )
        check("breaker_tripped", tripped, crashes=crashes,
              shed_status=status)
        time.sleep(1.5)  # past the cooldown; injected shots exhausted
        status, body, _ = child.analyze(payload)
        recovered = (
            status == 200
            and body.get("findings_swc") == reference["killbilly"]
        )
        status, ready, _ = _http("GET", child.base + "/readyz")
        check("breaker_recovered", recovered
              and ready.get("breakers", {}).get("toxic") == "closed",
              breakers=ready.get("breakers"))
    finally:
        child.stop()

    # -- scenario 4: per-request deadline -> partial, next unaffected
    child = _ServeChild()
    try:
        check("deadline_server_ready", child.wait_ready())
        tree = bench.chaos_tree_contract()
        status, body, _ = child.analyze({
            "code": tree, "name": "chaos_tree", "tx_count": 2,
            "deadline_s": 0.05, "source": "soak",
        })
        check("deadline_partial_report",
              status == 200 and body.get("partial") is True,
              status=status, partial=body.get("partial") if body else None)
        code, tx_count = corpus["killbilly"]
        status, body, _ = child.analyze({
            "code": code, "name": "killbilly", "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })
        check(
            "post_deadline_request_unaffected",
            status == 200 and body.get("partial") is False
            and body.get("findings_swc") == reference["killbilly"],
        )
    finally:
        child.stop()

    # -- scenario 5: queue overflow sheds with Retry-After -------------
    child = _ServeChild(extra_env={
        "MYTHRIL_TPU_SERVE_QUEUE_INTERACTIVE": "1",
    })
    try:
        check("overflow_server_ready", child.wait_ready())
        import threading

        tree = bench.chaos_tree_contract()
        slow = {"code": tree, "name": "chaos_tree", "tx_count": 2,
                "deadline_s": 60, "source": "soak"}
        background = [
            threading.Thread(target=child.analyze, args=(slow,))
            for _ in range(2)
        ]
        for thread in background:
            thread.start()
        time.sleep(0.5)  # one executing, one queued (cap 1)
        sheds = [child.analyze(slow, timeout=30) for _ in range(3)]
        shed_hit = [
            (status, body["error"]["code"], headers.get("Retry-After"))
            for status, body, headers in sheds
            if status == 503 and body and "error" in body
        ]
        check(
            "queue_overflow_sheds_with_retry_after",
            any(code == "queue_full" and retry for _, code, retry
                in shed_hit),
            sheds=[s[0] for s in sheds],
        )
        for thread in background:
            thread.join(timeout=240)
        status, ready, _ = _http("GET", child.base + "/readyz")
        check("overflow_server_survives",
              status == 200 and ready.get("ready") is True,
              rss_alive=True)
    finally:
        child.stop()

    if failures:
        print(json.dumps({"serve_soak_failures": failures}))
        return 1
    print(json.dumps({"serve_soak_ok": True, "scenarios": 5}))
    return 0


# ---------------------------------------------------------------------------
# --multihost: soak the serving fabric (serve + remote workers)
# ---------------------------------------------------------------------------


def _routable_ip():
    """A non-loopback address of this host (the fabric listen target),
    or None when the host has only loopback."""
    import socket

    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            sock.connect(("10.255.255.255", 1))  # no packet is sent
            ip = sock.getsockname()[0]
        return None if ip.startswith("127.") else ip
    except OSError:
        return None


class _WorkerChild:
    """One ``myth worker --connect`` subprocess."""

    def __init__(self, connect, secret_file, reconnect=60,
                 extra_env=None):
        myth = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "myth",
        )
        env = dict(os.environ)
        env.pop("MYTHRIL_TPU_FAULT", None)
        env.pop("MYTHRIL_TPU_KILL_AT", None)
        env.update(extra_env or {})
        self.proc = subprocess.Popen(
            [sys.executable, myth, "worker", "--connect", connect,
             "--secret-file", secret_file,
             "--reconnect", str(reconnect)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    def sigkill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def stop(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)


def _wait_seats(base, want, timeout_s=SERVE_READY_TIMEOUT_S):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        status, body, _ = _http("GET", base + "/debug/fleet", timeout=5)
        fabric = (body or {}).get("fabric") or {}
        if status == 200 and fabric.get("seats", 0) >= want:
            return True
        time.sleep(0.5)
    return False


def multihost_soak_main() -> int:
    """The --multihost driver: one ``myth serve`` endpoint fronting a
    >=2-process fleet on a non-loopback listener with an authenticated
    handshake.  Worker SIGKILL mid-request, a hostile unauthenticated
    peer, and a coordinator SIGKILL+restart must all be invisible to
    clients at findings parity; ``MYTHRIL_TPU_FLEET=0`` must yield the
    exact single-process path."""
    import threading

    import bench

    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    ip = _routable_ip()
    if ip is None:
        # loopback-only host: the fabric still runs authenticated, the
        # non-loopback bind refusal is covered by tests/test_fabric.py
        print(json.dumps({"note": "no routable interface; running the "
                          "fabric on loopback"}), file=sys.stderr)
        ip = "127.0.0.1"
    secret_path = tempfile.mktemp(prefix="mtpu-secret-")
    with open(secret_path, "w") as fh:
        fh.write("%032x\n" % random.SystemRandom().getrandbits(128))

    print("multihost soak: computing in-process CLI reference ...",
          file=sys.stderr)
    reference = _serve_reference()
    print(json.dumps({"reference": reference}), file=sys.stderr)
    corpus = {name: (code, tx) for name, code, tx, _ in bench._corpus()}

    fleet_port = _free_port()
    connect = f"{ip}:{fleet_port}"
    serve_args = ["--fleet-listen", connect,
                  "--secret-file", secret_path]
    child = _ServeChild(extra_args=serve_args)
    workers = [_WorkerChild(connect, secret_path) for _ in range(2)]
    try:
        check("fabric_server_ready", child.wait_ready())
        check("two_remote_seats_attached",
              _wait_seats(child.base, want=2), listen=connect)

        # -- scenario 1: findings parity through the fabric ------------
        parity = {}
        modes = {}
        for name, (code, tx_count) in corpus.items():
            status, body, _ = child.analyze({
                "code": code, "name": name, "tx_count": tx_count,
                "deadline_s": 240, "source": "soak",
            })
            parity[name] = (
                status == 200
                and body.get("findings_swc") == reference[name]
            )
            modes[name] = body.get("mode") if body else None
        _s, fleet_body, _h = _http("GET", child.base + "/debug/fleet")
        routed = ((fleet_body or {}).get("fabric") or {}).get("routed", 0)
        check("fabric_findings_parity",
              all(parity.values()) and routed >= 1,
              per_contract=parity, modes=modes, routed=routed)

        # -- scenario 2: SIGKILL a worker mid-request ------------------
        tree = bench.chaos_tree_contract()
        result = {}

        def _fire():
            result["resp"] = child.analyze({
                "code": tree, "name": "chaos_tree", "tx_count": 2,
                "deadline_s": 240, "source": "soak",
            })

        thread = threading.Thread(target=_fire)
        thread.start()
        time.sleep(2.0)  # let the lease land on a seat
        workers[0].sigkill()
        thread.join(timeout=300)
        status, body, _ = result.get("resp", (0, None, None))
        check(
            "worker_sigkill_invisible_to_client",
            status == 200 and body is not None
            and body.get("findings_swc") is not None,
            status=status,
            found=body.get("findings_swc") if body else None,
            mode=body.get("mode") if body else None,
        )

        # -- scenario 3: hostile unauthenticated peer ------------------
        import socket as socket_mod

        for payload in (b"\x00" * 64, b"GET / HTTP/1.1\r\n\r\n",
                        b"\xff" * 4096):
            try:
                with socket_mod.create_connection(
                    (ip, fleet_port), timeout=5
                ) as hostile:
                    hostile.sendall(payload)
                    hostile.settimeout(5)
                    try:
                        hostile.recv(4096)
                    except OSError:
                        pass
            except OSError:
                pass
        status, ready, _ = _http("GET", child.base + "/readyz")
        code, tx_count = corpus["killbilly"]
        astatus, abody, _ = child.analyze({
            "code": code, "name": "killbilly", "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })
        check(
            "hostile_peer_rejected_service_continues",
            status == 200 and ready.get("ready") is True
            and astatus == 200
            and abody.get("findings_swc") == reference["killbilly"],
            ready=status,
        )
    finally:
        child.stop()

    # -- scenario 4: coordinator SIGKILL mid-request, restart, workers
    # redial (--reconnect), parity re-asserted on the same ports ------
    serve_port = _free_port()
    child = _ServeChild(extra_args=serve_args, port=serve_port)
    try:
        check("restart_fabric_ready", child.wait_ready())
        _wait_seats(child.base, want=1)
        result = {}
        tree = bench.chaos_tree_contract()

        def _doomed():
            result["resp"] = _http(
                "POST", child.base + "/analyze",
                {"code": tree, "name": "chaos_tree", "tx_count": 2,
                 "deadline_s": 240, "source": "soak"},
                timeout=60,
            )

        thread = threading.Thread(target=_doomed)
        thread.start()
        time.sleep(1.0)
        child.sigkill()  # the coordinator dies mid-request
        thread.join(timeout=90)
    finally:
        child.stop()
    child = _ServeChild(extra_args=serve_args, port=serve_port)
    try:
        ready_again = child.wait_ready()
        seats_again = _wait_seats(child.base, want=1)
        code, tx_count = corpus["killbilly"]
        status, body, _ = child.analyze({
            "code": code, "name": "killbilly", "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })
        check(
            "coordinator_restart_workers_redial_parity",
            ready_again and seats_again and status == 200
            and body.get("findings_swc") == reference["killbilly"],
            ready=ready_again, seats=seats_again, status=status,
        )
    finally:
        child.stop()
        for worker in workers:
            worker.stop()

    # -- scenario 5: kill switch => exact single-process serve path ---
    child = _ServeChild(extra_args=serve_args,
                        extra_env={"MYTHRIL_TPU_FLEET": "0"})
    try:
        ready = child.wait_ready()
        _s, rbody, _h = _http("GET", child.base + "/readyz")
        code, tx_count = corpus["killbilly"]
        status, body, _ = child.analyze({
            "code": code, "name": "killbilly", "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })
        check(
            "kill_switch_single_process_path",
            ready and (rbody or {}).get("fabric") is None
            and status == 200
            and body.get("findings_swc") == reference["killbilly"]
            and body.get("mode") != "fabric",
            mode=body.get("mode") if body else None,
        )
    finally:
        child.stop()
        os.unlink(secret_path)

    if failures:
        print(json.dumps({"multihost_soak_failures": failures}))
        return 1
    print(json.dumps({"multihost_soak_ok": True, "scenarios": 6}))
    return 0


# ---------------------------------------------------------------------------
# --persist: soak the knowledge store (persist/)
# ---------------------------------------------------------------------------


def persist_soak_main() -> int:
    """The --persist driver: a shared ``--persist-dir`` must make warm
    state SURVIVE process restarts, SIGKILL mid-flush, and deliberate
    corruption — and gossip it across fabric seats — while findings
    never change and nothing ever crashes."""
    import glob
    import shutil

    import bench

    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    print("persist soak: computing in-process CLI reference ...",
          file=sys.stderr)
    reference = _serve_reference()
    print(json.dumps({"reference": reference}), file=sys.stderr)
    corpus_rows = bench._corpus()
    corpus = {name: (code, tx) for name, code, tx, _ in corpus_rows}
    kb_name, (kb_code, kb_tx) = "killbilly", corpus["killbilly"]
    alt_name = corpus_rows[1][0]  # any cache-miss contract
    alt_code, alt_tx = corpus[alt_name]

    persist_dir = tempfile.mkdtemp(prefix="mtpu-persist-soak-")
    penv = {"MYTHRIL_TPU_PERSIST_DIR": persist_dir,
            "MYTHRIL_TPU_PERSIST_FLUSH_S": "0"}

    def submit(child, name, code, tx_count):
        return child.analyze({
            "code": code, "name": name, "tx_count": tx_count,
            "deadline_s": 240, "source": "soak",
        })

    # -- scenario 1: populate cold, then a FRESH process answers the
    # same submission from the durable report cache at parity ----------
    child = _ServeChild(extra_env=penv)
    try:
        check("persist_server_ready", child.wait_ready())
        status, body, _ = submit(child, kb_name, kb_code, kb_tx)
        check("cold_pass_parity",
              status == 200
              and body.get("findings_swc") == reference[kb_name],
              found=body.get("findings_swc") if body else None)
    finally:
        child.stop()
    child = _ServeChild(extra_env=penv)
    try:
        check("warm_restart_ready", child.wait_ready())
        status, body, _ = submit(child, kb_name, kb_code, kb_tx)
        check("warm_restart_cached_parity",
              status == 200
              and body.get("findings_swc") == reference[kb_name]
              and body.get("cached") is True,
              cached=body.get("cached") if body else None)
    finally:
        child.stop()

    # -- scenario 2: SIGKILL lands exactly at the flush point (the
    # armed kill fires inside SegmentStore.flush) — the restarted
    # process must stay warm for what WAS flushed and simply re-derive
    # what was torn away, at parity throughout ------------------------
    child = _ServeChild(extra_env=dict(
        penv, MYTHRIL_TPU_KILL_AT="persist_flush",
    ))
    try:
        check("killat_server_ready", child.wait_ready())
        status, body, _ = submit(child, alt_name, alt_code, alt_tx)
        # the process SIGKILLs mid-request: any client-visible outcome
        # short of a wrong verdict is acceptable here
        deadline = time.time() + 30
        while time.time() < deadline and child.proc.poll() is None:
            time.sleep(0.2)
        check("sigkill_mid_flush_landed", child.proc.poll() is not None,
              status=status)
    finally:
        child.stop()
    child = _ServeChild(extra_env=penv)
    try:
        check("restart_after_torn_flush_ready", child.wait_ready())
        status_w, body_w, _ = submit(child, kb_name, kb_code, kb_tx)
        status_c, body_c, _ = submit(child, alt_name, alt_code, alt_tx)
        check(
            "torn_flush_parity",
            status_w == 200 and status_c == 200
            and body_w.get("findings_swc") == reference[kb_name]
            and body_w.get("cached") is True
            and body_c.get("findings_swc") == reference[alt_name],
            warm_cached=body_w.get("cached") if body_w else None,
            alt_found=body_c.get("findings_swc") if body_c else None,
        )
    finally:
        child.stop()

    # -- scenario 3: bit-flip every segment — the store must quarantine
    # and the process must degrade to a cold start at exact parity ----
    segments = sorted(glob.glob(os.path.join(persist_dir, "seg-*.bin")))
    check("store_has_segments", bool(segments), n=len(segments))
    for path in segments:
        mid = os.path.getsize(path) // 2
        with open(path, "r+b") as fh:
            fh.seek(mid)
            byte = fh.read(1) or b"\x00"
            fh.seek(mid)
            fh.write(bytes([byte[0] ^ 0xFF]))
    child = _ServeChild(extra_env=penv)
    try:
        check("corrupt_store_server_ready", child.wait_ready())
        status, body, _ = submit(child, kb_name, kb_code, kb_tx)
        quarantined = glob.glob(
            os.path.join(persist_dir, "*.quarantined")
        )
        check(
            "corrupt_store_cold_parity",
            status == 200
            and body.get("findings_swc") == reference[kb_name]
            and not body.get("cached")
            and len(quarantined) >= len(segments),
            quarantined=len(quarantined),
            found=body.get("findings_swc") if body else None,
        )
    finally:
        child.stop()

    # -- scenario 4: kill switch — the dir is set but MYTHRIL_TPU_
    # PERSIST=0 must restore the exact in-memory-only path (no reads,
    # no writes, no cached answers) -----------------------------------
    def _segment_count():
        return len(glob.glob(os.path.join(persist_dir, "seg-*.bin")))

    before = _segment_count()
    child = _ServeChild(extra_env=dict(penv, MYTHRIL_TPU_PERSIST="0"))
    try:
        check("kill_switch_server_ready", child.wait_ready())
        status, body, _ = submit(child, kb_name, kb_code, kb_tx)
        status2, body2, _ = submit(child, kb_name, kb_code, kb_tx)
        check(
            "kill_switch_inert",
            status == 200 and status2 == 200
            and body.get("findings_swc") == reference[kb_name]
            and not body.get("cached") and not body2.get("cached")
            and _segment_count() == before,
            segments_before=before, segments_after=_segment_count(),
        )
    finally:
        child.stop()

    # -- scenario 5: two-seat fabric — knowledge deltas ride worker
    # heartbeats through the coordinator; findings parity through the
    # fabric with persistence + gossip armed on every process ---------
    secret_path = tempfile.mktemp(prefix="mtpu-persist-secret-")
    with open(secret_path, "w") as fh:
        fh.write("%032x\n" % random.SystemRandom().getrandbits(128))
    gossip_dir = tempfile.mkdtemp(prefix="mtpu-persist-gossip-")
    genv = {"MYTHRIL_TPU_PERSIST_DIR": gossip_dir,
            "MYTHRIL_TPU_PERSIST_FLUSH_S": "0",
            "MYTHRIL_TPU_PERSIST_GOSSIP": "1"}
    fleet_port = _free_port()
    connect = f"127.0.0.1:{fleet_port}"
    child = _ServeChild(
        extra_env=genv,
        extra_args=["--fleet-listen", connect,
                    "--secret-file", secret_path],
    )
    workers = [_WorkerChild(connect, secret_path, extra_env=genv)
               for _ in range(2)]
    try:
        check("gossip_fabric_ready", child.wait_ready())
        check("gossip_two_seats", _wait_seats(child.base, want=2))
        parity = {}
        for name, (code, tx_count) in corpus.items():
            status, body, _ = submit(child, name, code, tx_count)
            parity[name] = (
                status == 200
                and body.get("findings_swc") == reference[name]
            )
        check("gossip_fabric_parity", all(parity.values()),
              per_contract=parity)
    finally:
        child.stop()
        for worker in workers:
            worker.stop()
        try:
            os.unlink(secret_path)
        except OSError:
            pass
    shutil.rmtree(gossip_dir, ignore_errors=True)
    shutil.rmtree(persist_dir, ignore_errors=True)

    if failures:
        print(json.dumps({"persist_soak_failures": failures}))
        return 1
    print(json.dumps({"persist_soak_ok": True, "scenarios": 5}))
    return 0


# ---------------------------------------------------------------------------
# --fleet: soak the frontier fleet
# ---------------------------------------------------------------------------

FLEET_TX_COUNT = 3  # >= 2 worker-side boundaries => mid-corpus kills


def _fleet_round(workers, env=None, arm=None):
    """One chaos-tree analysis with the given fleet width, env
    overrides for the round (workers inherit them), and an optional
    coordinator-side armed fault.  Returns (found, row)."""
    from mythril_tpu.parallel import fleet as fleet_mod
    from mythril_tpu.resilience import faults
    from mythril_tpu.support.support_args import args

    import bench

    saved_env = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    saved_workers = args.fleet_workers
    args.fleet_workers = workers
    faults.reset_for_tests()
    fleet_mod.reset_fleet_for_tests()
    if arm:
        point, kwargs = arm
        faults.get_fault_plane().arm(point, **kwargs)
    try:
        found, row = bench._analyze_one(
            "chaos_tree", bench.chaos_tree_contract(), FLEET_TX_COUNT,
            execution_timeout=300, max_depth=128,
        )
    finally:
        args.fleet_workers = saved_workers
        faults.reset_for_tests()
        for key, value in saved_env.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
    return found, row


def fleet_soak_main() -> int:
    """The --fleet driver: preempt, partition, and drop — the fleet
    must recover, fence, and degrade; findings never change."""
    import logging

    logging.basicConfig(level=logging.ERROR)
    _kr_configure()  # same device-path knobs as the other soaks

    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    print("fleet soak: --workers 0 reference pass ...", file=sys.stderr)
    reference, _row = _fleet_round(workers=0)
    reference = sorted(reference)
    check("reference_found_swc106", "106" in reference,
          found=reference)

    rounds = [
        # clean sharded run: parity both ways against the reference
        ("fleet_clean_parity", {}, None, {}),
        # SIGKILL every worker at its FIRST boundary (spot preemption
        # at lease start); replacements re-lease from the journals
        ("worker_kill_first_boundary",
         {"MYTHRIL_TPU_FAULT": "worker_kill:1"}, None,
         {"worker_deaths": 1}),
        # SIGKILL mid-corpus: the second boundary the worker reaches,
        # so at least one transaction's progress is already journaled
        ("worker_kill_mid_corpus",
         {"MYTHRIL_TPU_FAULT": "worker_kill:1:1"}, None,
         {"worker_deaths": 1}),
        # partition: heartbeats eaten => lease expiry => re-lease under
        # a bumped epoch; the zombie's stale-epoch gossip/result replay
        # MUST be fenced without changing any verdict
        ("lease_partition_stale_gossip_fenced",
         {"MYTHRIL_TPU_FLEET_HEARTBEAT_S": "0.1",
          "MYTHRIL_TPU_FLEET_LEASE_TTL_S": "0.6"},
         ("lease_partition", {"times": 99}),
         {"worker_deaths": 1, "gossip_dropped_stale": 1}),
        # lossy gossip channel: knowledge is an accelerant, never
        # load-bearing
        ("gossip_drop_harmless", {},
         ("gossip_drop", {"times": 99}), {}),
    ]
    for scenario, env, arm, minimums in rounds:
        began = time.time()
        try:
            found, row = _fleet_round(workers=2, env=env, arm=arm)
        except Exception as exc:  # noqa: BLE001 — an uncaught scenario
            #                       failure must force a nonzero exit
            check(scenario, False, error=f"{type(exc).__name__}: {exc}")
            continue
        detail = {
            "wall_s": round(time.time() - began, 1),
            "found": sorted(found),
            "fleet": {k: v for k, v in row.items()
                      if k.startswith("fleet_") and v},
        }
        ok = sorted(found) == reference
        for counter, floor in minimums.items():
            ok = ok and row.get(f"fleet_{counter}", 0) >= floor
        check(scenario, ok, **detail)

    # kill switch: --workers 2 under MYTHRIL_TPU_FLEET=0 must be the
    # exact single-process pipeline (no leases, identical findings)
    began = time.time()
    try:
        found, row = _fleet_round(
            workers=2, env={"MYTHRIL_TPU_FLEET": "0"}
        )
        check(
            "kill_switch_exact_single_process",
            sorted(found) == reference
            and row.get("fleet_leases", 0) == 0,
            wall_s=round(time.time() - began, 1),
            found=sorted(found), leases=row.get("fleet_leases"),
        )
    except Exception as exc:  # noqa: BLE001
        check("kill_switch_exact_single_process", False,
              error=f"{type(exc).__name__}: {exc}")

    if failures:
        print(json.dumps({"fleet_soak_failures": failures}))
        return 1
    print(json.dumps({"fleet_soak_ok": True,
                      "rounds": len(rounds) + 2}))
    return 0


# ---------------------------------------------------------------------------
# --wild: soak the wild-bytecode envelope (triage + governor + pool)
# ---------------------------------------------------------------------------

WILD_SWEEP_LIMIT = 12  # fixtures per sweep round (whole corpus once)


def _wild_scripts_dir():
    return os.path.dirname(os.path.abspath(__file__))


def _wild_sweep_cmd(journal, out, resume=False, extra=()):
    cmd = [
        sys.executable,
        os.path.join(_wild_scripts_dir(), "corpus_sweep.py"),
        "--limit", str(WILD_SWEEP_LIMIT), "--deadline-s", "3",
        "--max-depth", "16", "--journal", journal, "--out", out,
    ]
    if resume:
        cmd.append("--resume")
    return cmd + list(extra)


def wild_soak_main() -> int:
    """The --wild driver: the never-crash envelope under abuse — a
    flapping provider mid-load, SIGKILL mid-sweep with a journal
    resume, and a governor breach whose partial verdict must report a
    findings SUBSET of the unbudgeted run."""
    import logging

    logging.basicConfig(level=logging.ERROR)
    sys.path.insert(0, _wild_scripts_dir())
    import corpus_sweep

    from mythril_tpu.ethereum.interface.rpc.client import (
        EthJsonRpc,
        ProviderPool,
    )
    from mythril_tpu.mythril.mythril_disassembler import MythrilDisassembler
    from mythril_tpu.resilience import faults
    from mythril_tpu.resilience.telemetry import resilience_stats

    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    fixtures = dict(corpus_sweep.load_fixtures(corpus_sweep.FIXTURE_DIR))

    # -- scenario 1: provider flap mid-load ---------------------------
    # two fake providers serve the proxy fixture + its implementation;
    # the rpc_flap fault kills attempts mid-chain and the pool must
    # rotate through it to the same triage verdict as a calm load
    class _FixtureClient(EthJsonRpc):
        def _call(self, method, params=None):
            addr = (params or ["0x"])[0].lower()
            if addr == "0x" + "c0de" * 10:
                return "0x" + fixtures["proxy_impl"].removeprefix("0x")
            return "0x" + fixtures["proxy_1167"].removeprefix("0x")

    def _load(flaps):
        faults.reset_for_tests()
        resilience_stats.reset()
        if flaps:
            faults.get_fault_plane().arm("rpc_flap", times=flaps)
        pool = ProviderPool(
            [_FixtureClient(host=f"fake{i}") for i in range(2)],
            breaker_fails=5,
        )
        _, contract = MythrilDisassembler(eth=pool).load_from_address(
            "0x" + "11" * 20
        )
        rotations = resilience_stats.rpc_provider_rotations
        faults.reset_for_tests()
        return contract, rotations

    try:
        calm, _ = _load(flaps=0)
        flapped, rotations = _load(flaps=2)
        check(
            "provider_flap_mid_load_parity",
            flapped.triage == calm.triage
            and flapped.code == calm.code
            and rotations >= 2
            and calm.triage.get("proxy_target") == "0x" + "c0de" * 10,
            rotations=rotations, triage=flapped.triage,
        )
    except Exception as exc:  # noqa: BLE001 — a crashed scenario fails
        check("provider_flap_mid_load_parity", False,
              error=f"{type(exc).__name__}: {exc}")

    # -- scenario 2: SIGKILL mid-sweep, then --resume from the journal
    workdir = tempfile.mkdtemp(prefix="mtpu-wild-")
    journal = os.path.join(workdir, "sweep.jsonl")
    out = os.path.join(workdir, "report.json")
    env = dict(os.environ)
    env.pop("MYTHRIL_TPU_FAULT", None)
    env.pop("MYTHRIL_TPU_KILL_AT", None)
    victim = subprocess.Popen(
        _wild_sweep_cmd(journal, out), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    deadline = time.time() + 300
    journaled = 0
    while time.time() < deadline and victim.poll() is None:
        try:
            with open(journal) as fh:
                journaled = sum(1 for line in fh if line.strip())
        except OSError:
            journaled = 0
        if journaled >= 3:
            break
        time.sleep(0.1)
    killed = victim.poll() is None and journaled >= 3
    if killed:
        victim.kill()
    victim.wait(timeout=30)
    check("sigkill_mid_sweep_landed", killed, journaled=journaled)

    resumed = subprocess.run(
        _wild_sweep_cmd(journal, out, resume=True), env=env,
        capture_output=True, text=True, timeout=600,
    )
    try:
        with open(out) as fh:
            report = json.load(fh)
    except (OSError, ValueError):
        report = {}
    with open(journal) as fh:
        replayed = {json.loads(line)["id"] for line in fh if line.strip()}
    check(
        "journal_resume_completes_sweep",
        resumed.returncode == 0
        and report.get("contracts") == WILD_SWEEP_LIMIT
        and not report.get("crashes")
        and len(replayed) == WILD_SWEEP_LIMIT,
        exit=resumed.returncode, contracts=report.get("contracts"),
        unique_journaled=len(replayed),
        survival_pct=report.get("survival_pct"),
    )

    # -- scenario 3: governor breach => partial whose findings are a
    # SUBSET of the unbudgeted run on the same contract ---------------
    # the overflow fixture fans out enough states under two
    # transactions to ride the ladder all the way to drain_partial
    name = "unchecked_add"
    code = fixtures[name]
    try:
        free = corpus_sweep.analyze_one(
            name, code, deadline_s=60, max_depth=24, tx_count=2
        )
        os.environ["MYTHRIL_TPU_GOVERNOR_STATES"] = "1"
        try:
            squeezed = corpus_sweep.analyze_one(
                name, code, deadline_s=60, max_depth=24, tx_count=2
            )
        finally:
            os.environ.pop("MYTHRIL_TPU_GOVERNOR_STATES", None)
        check(
            "governor_breach_partial_findings_subset",
            free["verdict"] in ("full", "partial")
            and squeezed["verdict"] == "partial"
            and squeezed.get("reason") == "governor"
            and set(squeezed["findings"]) <= set(free["findings"])
            and (squeezed.get("governor") or {}).get("rungs"),
            free=free["verdict"], free_findings=free["findings"],
            squeezed_findings=squeezed["findings"],
            rungs=(squeezed.get("governor") or {}).get("rungs"),
        )
    except Exception as exc:  # noqa: BLE001
        check("governor_breach_partial_findings_subset", False,
              error=f"{type(exc).__name__}: {exc}")

    if failures:
        print(json.dumps({"wild_soak_failures": failures}))
        return 1
    print(json.dumps({"wild_soak_ok": True, "scenarios": 3}))
    return 0


def watch_soak_main() -> int:
    """The --watch driver: live-chain ingestion under abuse — a reorg
    plus a provider flap mid-follow, SIGKILL mid-follow with a
    ``--resume`` that must finish the chain, and a reorg landing right
    at the head.  The bar is the exactly-once contract against the
    mock chain's published ground truth (``GET /__expect``): every
    unique runtime digest freshly analyzed at most once and answered
    at least once — a re-submission after a crash answers from the
    shared report cache (``cached: true`` is dedup, not a duplicate
    analysis) — with zero watcher crashes and zero missed digests."""
    failures = []

    def check(scenario, ok, **detail):
        row = {"scenario": scenario, "ok": bool(ok), **detail}
        print(json.dumps(row))
        if not ok:
            failures.append(row)

    scripts_dir = os.path.dirname(os.path.abspath(__file__))
    myth = os.path.join(os.path.dirname(scripts_dir), "myth")

    def start_chain(**kw):
        cmd = [sys.executable,
               os.path.join(scripts_dir, "mock_chain.py")]
        for key, value in kw.items():
            cmd += ["--" + key.replace("_", "-"), str(value)]
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True)
        info = json.loads(proc.stdout.readline())["mock_chain"]
        return proc, info["url"]

    def watch_cmd(url, workdir, until, resume=False):
        cmd = [sys.executable, myth, "watch", "--rpc", url,
               "--journal", os.path.join(workdir, "cursor.jsonl"),
               "--findings-out", os.path.join(workdir, "findings.jsonl"),
               "--until-block", str(until), "--poll-s", "0.05",
               "--confirmations", "0", "--deadline-s", "2",
               "--tx-count", "1"]
        return cmd + (["--resume"] if resume else [])

    def watch_env(workdir):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MYTHRIL_TPU_FAULT", None)
        env.pop("MYTHRIL_TPU_KILL_AT", None)
        # the shared report cache is what turns a crash-window
        # re-submission into a cached answer instead of a re-analysis
        env["MYTHRIL_TPU_PERSIST_DIR"] = os.path.join(workdir, "persist")
        env["MYTHRIL_TPU_PERSIST_FLUSH_S"] = "0"
        return env

    def summary_of(proc_result):
        for line in reversed(proc_result.stdout.strip().splitlines()):
            if line.startswith("{") and "watch_summary" in line:
                return json.loads(line)["watch_summary"]
        return {}

    def findings_ledger(workdir):
        """(fresh-analysis counts per digest, answered digests)."""
        fresh, answered = {}, set()
        try:
            with open(os.path.join(workdir, "findings.jsonl")) as fh:
                for line in fh:
                    row = json.loads(line)
                    if row.get("status") != "analyzed":
                        continue
                    answered.add(row["digest"])
                    if not row.get("cached"):
                        fresh[row["digest"]] = \
                            fresh.get(row["digest"], 0) + 1
        except OSError:
            pass
        return fresh, answered

    def exactly_once(workdir, url):
        _status, expect, _h = _http("GET", url + "/__expect", timeout=10)
        expected = set((expect or {}).get("unique_digests") or ())
        fresh, answered = findings_ledger(workdir)
        doubled = sorted(d for d, n in fresh.items() if n > 1)
        return (
            expected == answered and not doubled and bool(expected),
            {
                "expected": len(expected),
                "answered": len(answered),
                "missed": len(expected - answered),
                "invented": len(answered - expected),
                "double_analyzed": len(doubled),
            },
        )

    # -- scenario 1: reorg + provider flap mid-follow -----------------
    workdir = tempfile.mkdtemp(prefix="mtpu-watch-")
    chain, url = start_chain(blocks=40, deployments=80, reorg_at=20,
                             reorg_depth=3, head_step=3,
                             flap_at_head=27, flap_requests=4)
    try:
        done = subprocess.run(
            watch_cmd(url, workdir, until=40), env=watch_env(workdir),
            capture_output=True, text=True, timeout=420,
        )
        summary = summary_of(done)
        once_ok, once = exactly_once(workdir, url)
        check(
            "reorg_and_flap_mid_follow_exactly_once",
            done.returncode == 0 and once_ok
            and summary.get("reorgs", 0) >= 1
            and summary.get("dedup_hits", 0) > 0
            and summary.get("errors") == 0,
            exit=done.returncode, reorgs=summary.get("reorgs"),
            dedup_hits=summary.get("dedup_hits"), **once,
        )
    except Exception as exc:  # noqa: BLE001 — a crashed scenario fails
        check("reorg_and_flap_mid_follow_exactly_once", False,
              error=f"{type(exc).__name__}: {exc}")
    finally:
        chain.kill()
        chain.wait(timeout=30)

    # -- scenario 2: SIGKILL mid-follow, --resume finishes the chain --
    workdir = tempfile.mkdtemp(prefix="mtpu-watch-")
    journal = os.path.join(workdir, "cursor.jsonl")
    chain, url = start_chain(blocks=60, deployments=120, reorg_at=30,
                             reorg_depth=3, head_step=3)
    try:
        victim = subprocess.Popen(
            watch_cmd(url, workdir, until=60), env=watch_env(workdir),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        deadline = time.time() + 300
        journaled = 0
        while time.time() < deadline and victim.poll() is None:
            try:
                with open(journal) as fh:
                    journaled = sum(1 for line in fh
                                    if '"block"' in line)
            except OSError:
                journaled = 0
            if journaled >= 8:
                break
            time.sleep(0.1)
        killed = victim.poll() is None and journaled >= 8
        if killed:
            victim.kill()
        victim.wait(timeout=30)
        check("sigkill_mid_follow_landed", killed, journaled=journaled)

        resumed = subprocess.run(
            watch_cmd(url, workdir, until=60, resume=True),
            env=watch_env(workdir), capture_output=True, text=True,
            timeout=420,
        )
        summary = summary_of(resumed)
        once_ok, once = exactly_once(workdir, url)
        check(
            "resume_after_sigkill_exactly_once",
            resumed.returncode == 0 and once_ok
            and summary.get("cursor") == 60
            and summary.get("reorgs", 0) >= 1
            and summary.get("dedup_hits", 0) > 0,
            exit=resumed.returncode, cursor=summary.get("cursor"),
            reorgs=summary.get("reorgs"),
            dedup_hits=summary.get("dedup_hits"), **once,
        )
    except Exception as exc:  # noqa: BLE001
        check("resume_after_sigkill_exactly_once", False,
              error=f"{type(exc).__name__}: {exc}")
    finally:
        chain.kill()
        chain.wait(timeout=30)

    # -- scenario 3: reorg landing at the head ------------------------
    workdir = tempfile.mkdtemp(prefix="mtpu-watch-")
    chain, url = start_chain(blocks=30, deployments=60, reorg_at=28,
                             reorg_depth=3, head_step=3)
    try:
        done = subprocess.run(
            watch_cmd(url, workdir, until=30), env=watch_env(workdir),
            capture_output=True, text=True, timeout=420,
        )
        summary = summary_of(done)
        once_ok, once = exactly_once(workdir, url)
        check(
            "reorg_at_head_exactly_once",
            done.returncode == 0 and once_ok
            and summary.get("reorgs", 0) >= 1
            and summary.get("errors") == 0,
            exit=done.returncode, reorgs=summary.get("reorgs"), **once,
        )
    except Exception as exc:  # noqa: BLE001
        check("reorg_at_head_exactly_once", False,
              error=f"{type(exc).__name__}: {exc}")
    finally:
        chain.kill()
        chain.wait(timeout=30)

    if failures:
        print(json.dumps({"watch_soak_failures": failures}))
        return 1
    print(json.dumps({"watch_soak_ok": True, "scenarios": 4}))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rounds", type=int, default=6)
    parser.add_argument("--seed", type=int, default=1337)
    parser.add_argument("--kill-resume", action="store_true",
                        help="checkpoint/resume chaos: SIGKILL at every "
                        "injection point, resume, demand identical "
                        "findings")
    parser.add_argument("--serve", action="store_true",
                        help="soak a live `myth serve` daemon: fault "
                        "injection parity, SIGKILL-restart, breaker "
                        "trip/recover, deadline partials, queue-"
                        "overflow shedding")
    parser.add_argument("--fleet", action="store_true",
                        help="soak the frontier fleet: worker SIGKILLs "
                        "at every reachable fleet fault point, "
                        "partition => stale-epoch fencing, gossip "
                        "loss, and the single-process kill switch — "
                        "findings parity asserted every round")
    parser.add_argument("--multihost", action="store_true",
                        help="soak the serving fabric: `myth serve` "
                        "fronting >=2 authenticated `myth worker` "
                        "processes on a non-loopback listener — "
                        "worker SIGKILL, hostile peer, coordinator "
                        "SIGKILL+restart, and the fleet kill switch, "
                        "all at findings parity")
    parser.add_argument("--persist", action="store_true",
                        help="soak the knowledge store: warm restart "
                        "from a shared --persist-dir, SIGKILL mid-"
                        "flush, bit-flipped segments => quarantine + "
                        "cold start, the MYTHRIL_TPU_PERSIST=0 kill "
                        "switch, and two-seat heartbeat gossip — "
                        "findings parity asserted everywhere")
    parser.add_argument("--wild", action="store_true",
                        help="soak the wild-bytecode envelope: provider "
                        "flap mid-load, SIGKILL mid-sweep + journal "
                        "resume, governor breach => partial verdict "
                        "whose findings are a subset of the unbudgeted "
                        "run")
    parser.add_argument("--watch", action="store_true",
                        help="soak live-chain ingestion: reorg + "
                        "provider flap mid-follow, SIGKILL mid-follow "
                        "+ --resume to completion, and a reorg at the "
                        "head — exactly-once asserted against the mock "
                        "chain's ground truth everywhere")
    parser.add_argument("--kr-child", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--kr-dir", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--kr-resume", action="store_true",
                        help=argparse.SUPPRESS)
    args_ns = parser.parse_args()
    # a soak runs several analyzer processes at once (serve + workers,
    # victim + resume) and a chip belongs to one process: every process
    # of a chaos run solves on the CPU, children inherit the pin
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args_ns.kr_child:
        return _kr_child(args_ns.kr_dir, args_ns.kr_resume)
    if args_ns.kill_resume:
        return kill_resume_main()
    if args_ns.serve:
        return serve_soak_main()
    if args_ns.fleet:
        return fleet_soak_main()
    if args_ns.multihost:
        return multihost_soak_main()
    if args_ns.persist:
        return persist_soak_main()
    if args_ns.wild:
        return wild_soak_main()
    if args_ns.watch:
        return watch_soak_main()
    rng = random.Random(args_ns.seed)

    import logging

    logging.basicConfig(level=logging.ERROR)
    from mythril_tpu.resilience import faults
    from mythril_tpu.support.support_args import args

    # the chaos schedule must actually reach the device paths
    args.device_min_lanes = 2
    args.device_force_dispatch = True
    args.word_probing = False
    # auto mode refuses gather dispatch on the CPU (the cpu_auto_skips
    # gate); pin the gather path explicitly so the injected dispatch
    # faults have a dispatch to hit
    os.environ.setdefault("MYTHRIL_TPU_PALLAS", "off")

    print("reference (fault-free) pass ...", file=sys.stderr)
    reference, _ = _analyze_corpus()
    print(json.dumps({"reference": reference}), file=sys.stderr)

    failures = []
    for round_no in range(args_ns.rounds):
        fault = rng.choice(sorted(SCHEDULE))
        arm_kwargs, env, arg_overrides = SCHEDULE[fault]
        saved = {k: os.environ.get(k) for k in env}
        saved_args = {k: getattr(args, k) for k in arg_overrides}
        os.environ.update(env)
        for key, value in arg_overrides.items():
            setattr(args, key, value)
        faults.reset_for_tests()
        faults.get_fault_plane().arm(fault, **arm_kwargs)
        began = time.time()
        error = None
        try:
            found, counters = _analyze_corpus()
        except Exception as exc:  # noqa: BLE001 — a scenario that
            #   raises before recording is a FAILED round, not a pass:
            #   it must land in `failures` and force the nonzero exit
            error = f"{type(exc).__name__}: {exc}"
            found, counters = None, {}
        finally:
            faults.reset_for_tests()
            for key, value in saved.items():
                if value is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = value
            for key, value in saved_args.items():
                setattr(args, key, value)
            from mythril_tpu.ops import device_health

            device_health.reset_for_tests()  # undo probe flaps
        parity = error is None and found == reference
        row = {
            "round": round_no,
            "fault": fault,
            "wall_s": round(time.time() - began, 1),
            "findings_parity": parity,
            "counters": {k: v for k, v in counters.items() if v},
        }
        if error is not None:
            row["error"] = error
        print(json.dumps(row))
        if not parity:
            failures.append(
                {"round": round_no, "fault": fault, "error": error,
                 "found": found, "reference": reference}
            )
    if failures:
        print(json.dumps({"chaos_failures": failures}))
        return 1
    print(json.dumps({"chaos_ok": True, "rounds": args_ns.rounds}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 — NOTHING that escapes a soak
        #   may exit 0: a crashed driver is a failed soak, and the CI
        #   gate keys on the exit status
        import traceback

        print(json.dumps({
            "soak_uncaught": traceback.format_exc()[-2000:],
        }))
        sys.exit(1)
