"""Tier-1 wiring of scripts/ffcheck.py + unit tests for the lint rules.

The repo-wide guard is the same pattern as tests/test_family_reexports:
``flexflow_tpu/`` must lint clean (zero unsuppressed findings) so a new
JAX/TPU hazard — a host sync sneaking into a traced function, a weak
``jnp.asarray`` at a jit boundary, a cache threaded through jit without
donation — fails CI at the PR that introduces it instead of shipping as
a silent 100x TPU slowdown.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from flexflow_tpu.analysis import get_rules, lint_paths, lint_source  # noqa: E402
from flexflow_tpu.analysis.lint import (  # noqa: E402
    FileContext,
    parse_suppressions,
)


def _load_ffcheck():
    path = os.path.join(REPO, "scripts", "ffcheck.py")
    spec = importlib.util.spec_from_file_location("ffcheck", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _codes(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# the CI-style guard: the package must stay clean


def test_package_lints_clean():
    findings = lint_paths([os.path.join(REPO, "flexflow_tpu")])
    assert not findings, (
        "new ffcheck findings (fix them, or suppress with a reason: "
        "`# ffcheck: disable=RULE -- why`):\n"
        + "\n".join(f.format() for f in findings)
    )


def test_ffcheck_script_exits_zero():
    mod = _load_ffcheck()
    assert mod.main([]) == 0


def test_ffcheck_list_rules():
    mod = _load_ffcheck()
    assert mod.main(["--list-rules"]) == 0
    # the catalog in analysis/__init__ must cover every registered rule
    import flexflow_tpu.analysis as analysis

    for rule in get_rules():
        assert rule.code in analysis.__doc__, (
            f"rule {rule.code} missing from the analysis/__init__.py "
            "rule catalog"
        )
        assert rule.slug in analysis.__doc__


def test_ffcheck_diff_mode(tmp_path):
    """--diff lints only files changed vs a base ref."""
    mod = _load_ffcheck()
    # vs HEAD there may be changes or not — the call must succeed either way
    rc = mod.main(["--diff", "HEAD"])
    assert rc in (0, 1)
    files = mod.changed_files("HEAD")
    assert isinstance(files, list)
    for f in files:
        assert f.endswith(".py") and os.path.exists(f)


# ---------------------------------------------------------------------------
# FF101 host-sync


def test_host_sync_in_jitted_function():
    src = (
        "import jax\nimport numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x)\n"
    )
    assert _codes(lint_source(src)) == ["FF101"]


def test_host_sync_item_and_device_get():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    y = x.item()\n"
        "    return jax.device_get(y)\n"
    )
    assert _codes(lint_source(src)) == ["FF101", "FF101"]


def test_host_sync_float_cast_of_traced_param():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, cfg):\n"
        "    return float(x) + float(cfg)\n"
    )
    # cfg is a conventional static — only float(x) is flagged
    assert _codes(lint_source(src)) == ["FF101"]


def test_host_sync_via_intra_file_call_graph():
    src = (
        "import jax\nimport numpy as np\n"
        "def helper(q):\n"
        "    return np.asarray(q)\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return helper(x)\n"
    )
    assert _codes(lint_source(src)) == ["FF101"]


def test_host_sync_ok_outside_trace():
    src = (
        "import numpy as np\n"
        "def host_fetch(x):\n"
        "    return np.asarray(x)\n"
    )
    assert lint_source(src) == []


def test_serve_protocol_functions_are_trace_roots():
    src = (
        "import numpy as np\n"
        "def serve_step(params, cache, tokens):\n"
        "    return np.asarray(tokens)\n"
    )
    assert _codes(lint_source(src)) == ["FF101"]
    # ...but serve_debug_activations is eager by design
    src2 = (
        "import numpy as np\n"
        "def serve_debug_activations(params, cache, tokens):\n"
        "    return np.asarray(tokens)\n"
    )
    assert lint_source(src2) == []


def test_engine_jit_chokepoint_marks_traced():
    """Functions handed to the engine's self._jit sanitizer chokepoint
    count as traced — the refactor must not blind the lint."""
    src = (
        "import numpy as np\n"
        "class E:\n"
        "    def g(self):\n"
        "        def step(params, cache):\n"
        "            return np.asarray(params)\n"
        "        self._steps['k'] = self._jit(step, key='k',"
        " donate_argnums=(1,))\n"
    )
    assert _codes(lint_source(src)) == ["FF101"]


# ---------------------------------------------------------------------------
# FF102 tracer-control-flow


def test_tracer_control_flow_if():
    src = (
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if jnp.any(x > 0):\n"
        "        x = x + 1\n"
        "    return x\n"
    )
    assert _codes(lint_source(src)) == ["FF102"]


def test_tracer_control_flow_static_branch_ok():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, mask=None):\n"
        "    if mask is None:\n"
        "        x = x + 1\n"
        "    return x\n"
    )
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# FF103 weak-dtype


def test_weak_dtype_flags_bare_asarray():
    src = "import jax.numpy as jnp\nx = jnp.asarray([1, 2])\n"
    assert _codes(lint_source(src)) == ["FF103"]


def test_weak_dtype_ok_with_dtype():
    src = (
        "import jax.numpy as jnp\n"
        "a = jnp.asarray([1, 2], dtype=jnp.int32)\n"
        "b = jnp.asarray([1, 2], jnp.int32)\n"   # positional dtype
        "c = jnp.asarray(jnp.zeros((2,)))\n"      # already a jax value
    )
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# FF104 unordered-iteration


def test_unordered_iteration_set_literal():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    for s in {1, 2, 3}:\n"
        "        x = x + s\n"
        "    return x\n"
    )
    assert _codes(lint_source(src)) == ["FF104"]


def test_unordered_iteration_list_ok():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    for s in [1, 2, 3]:\n"
        "        x = x + s\n"
        "    return x\n"
    )
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# FF105 missing-donation


def test_missing_donation_on_cache_param():
    src = (
        "import jax\n"
        "def step(params, cache, x):\n"
        "    return cache\n"
        "f = jax.jit(step)\n"
    )
    assert _codes(lint_source(src)) == ["FF105"]


def test_missing_donation_ok_with_donate():
    src = (
        "import jax\n"
        "def step(params, cache, x):\n"
        "    return cache\n"
        "f = jax.jit(step, donate_argnums=(1,))\n"
    )
    assert lint_source(src) == []


def test_missing_donation_cache_hook_attribute():
    src = "import jax\nf = jax.jit(model.commit_kv_paged)\n"
    assert _codes(lint_source(src)) == ["FF105"]


# ---------------------------------------------------------------------------
# FF106 static-hashability


def test_static_hashability_list_default():
    src = (
        "import jax, functools\n"
        "@functools.partial(jax.jit, static_argnames=('shape',))\n"
        "def g(x, shape=[1, 2]):\n"
        "    return x\n"
    )
    assert _codes(lint_source(src)) == ["FF106"]


def test_static_hashability_tuple_ok():
    src = (
        "import jax, functools\n"
        "@functools.partial(jax.jit, static_argnames=('shape',))\n"
        "def g(x, shape=(1, 2)):\n"
        "    return x\n"
    )
    assert lint_source(src) == []


def test_static_hashability_argnums():
    src = (
        "import jax\n"
        "def g(x, opts={}):\n"
        "    return x\n"
        "f = jax.jit(g, static_argnums=(1,))\n"
    )
    assert _codes(lint_source(src)) == ["FF106"]


# ---------------------------------------------------------------------------
# suppressions


def test_suppression_same_line():
    src = (
        "import jax.numpy as jnp\n"
        "x = jnp.asarray([1])  # ffcheck: disable=FF103 -- test fixture\n"
    )
    assert lint_source(src) == []


def test_suppression_by_slug_and_line_above():
    src = (
        "import jax.numpy as jnp\n"
        "# ffcheck: disable=weak-dtype -- dtype pinned upstream\n"
        "x = jnp.asarray([1])\n"
    )
    assert lint_source(src) == []


def test_suppression_file_level_and_all():
    src = (
        "# ffcheck: disable-file=FF103\n"
        "import jax.numpy as jnp\n"
        "x = jnp.asarray([1])\n"
        "y = jnp.asarray([2])\n"
    )
    assert lint_source(src) == []
    src_all = (
        "import jax.numpy as jnp\n"
        "x = jnp.asarray([1])  # ffcheck: disable=all\n"
    )
    assert lint_source(src_all) == []


def test_suppression_wrong_rule_does_not_hide():
    src = (
        "import jax.numpy as jnp\n"
        "x = jnp.asarray([1])  # ffcheck: disable=FF101\n"
    )
    assert _codes(lint_source(src)) == ["FF103"]


def test_suppression_reason_parsing():
    lines, file_rules = parse_suppressions(
        "x = 1  # ffcheck: disable=FF101,host-sync -- because reasons\n"
    )
    assert lines[1] == {"FF101", "host-sync"}
    assert file_rules == set()


def test_with_suppressed_reports_everything():
    src = (
        "import jax.numpy as jnp\n"
        "x = jnp.asarray([1])  # ffcheck: disable=FF103 -- hidden\n"
    )
    assert _codes(lint_source(src, with_suppressed=True)) == ["FF103"]


# ---------------------------------------------------------------------------
# meta: the analyzer must actually SEE the engine's traced surface


def test_engine_nested_steps_are_traced():
    """engine.py's nested `step` closures (jitted via self._jit under
    one shared name) must be in the traced set — otherwise the
    host-sync/control-flow rules silently stop covering the hot path."""
    path = os.path.join(REPO, "flexflow_tpu", "serve", "engine.py")
    ctx = FileContext(path, open(path).read())
    traced_names = {fn.name for fn in ctx.traced}
    assert "step" in traced_names, traced_names
    assert "speculate" in traced_names, traced_names


def test_model_serve_protocol_is_traced():
    path = os.path.join(REPO, "flexflow_tpu", "models", "transformer.py")
    ctx = FileContext(path, open(path).read())
    traced_names = {fn.name for fn in ctx.traced}
    for name in ("serve_step", "serve_step_paged", "commit_kv_paged",
                 "copy_page_kv", "forward"):
        assert name in traced_names, (name, sorted(traced_names))
    assert "serve_debug_activations" not in traced_names


def test_syntax_error_reported_not_crashed(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(:\n")
    findings = lint_paths([str(bad)])
    assert [f.rule for f in findings] == ["FF000"]


# ---------------------------------------------------------------------------
# FF109 wall-clock-in-step-logic

CONTRACT_PATH = "flexflow_tpu/serve/cluster/health.py"


def test_wall_clock_flagged_in_contract_files():
    src = (
        "import time\n"
        "def decide():\n"
        "    return time.time()\n"
    )
    assert _codes(lint_source(src, path=CONTRACT_PATH)) == ["FF109"]


def test_wall_clock_sleep_and_monotonic_flagged():
    src = (
        "import time\n"
        "def f():\n"
        "    time.sleep(0.1)\n"
        "    return time.monotonic()\n"
    )
    assert _codes(lint_source(src, path=CONTRACT_PATH)) == [
        "FF109", "FF109",
    ]


def test_wall_clock_argless_datetime_now_flagged():
    src = (
        "from datetime import datetime, timezone\n"
        "def f():\n"
        "    a = datetime.now()\n"
        "    b = datetime.now(timezone.utc)\n"  # tz-carrying: not flagged
        "    return a, b\n"
    )
    assert _codes(lint_source(src, path=CONTRACT_PATH)) == ["FF109"]


def test_wall_clock_perf_counter_allowed():
    src = (
        "import time\n"
        "def measure():\n"
        "    return time.perf_counter()\n"
    )
    assert lint_source(src, path=CONTRACT_PATH) == []


def test_wall_clock_ok_outside_contract_set():
    src = (
        "import time\n"
        "def f():\n"
        "    return time.time()\n"
    )
    assert lint_source(src, path="flexflow_tpu/serve/engine.py") == []


def test_wall_clock_suppression():
    src = (
        "import time\n"
        "def f():\n"
        "    # ffcheck: disable=FF109 -- test fixture\n"
        "    time.sleep(1)\n"
    )
    assert lint_source(src, path=CONTRACT_PATH) == []


# ---------------------------------------------------------------------------
# FF110 unguarded-shared-state


def _threaded_class(init_extra="", loop_body="", read_body=""):
    return (
        "import threading\n"
        "class T:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        f"{init_extra}"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop).start()\n"
        "    def _loop(self):\n"
        f"{loop_body}"
        "    def read(self):\n"
        f"{read_body}"
    )


def test_unguarded_shared_attr_flagged():
    src = _threaded_class(
        init_extra="        self._q = []\n",
        loop_body="        self._q.append(1)\n",
        read_body="        return len(self._q)\n",
    )
    assert _codes(lint_source(src)) == ["FF110"]


def test_guarded_registry_inline_clean():
    src = _threaded_class(
        init_extra="        self._q = []  # ffcheck: guarded-by=_lock\n",
        loop_body=(
            "        with self._lock:\n"
            "            self._q.append(1)\n"
        ),
        read_body=(
            "        with self._lock:\n"
            "            return len(self._q)\n"
        ),
    )
    assert lint_source(src) == []


def test_guarded_registry_bulk_form():
    src = _threaded_class(
        init_extra=(
            "        # ffcheck: guarded-by[_lock]=_q\n"
            "        self._q = []\n"
        ),
        loop_body=(
            "        with self._lock:\n"
            "            self._q.append(1)\n"
        ),
        read_body=(
            "        with self._lock:\n"
            "            return len(self._q)\n"
        ),
    )
    assert lint_source(src) == []


def test_registered_attr_scope_violation_flagged():
    src = _threaded_class(
        init_extra="        self._q = []  # ffcheck: guarded-by=_lock\n",
        loop_body=(
            "        with self._lock:\n"
            "            self._q.append(1)\n"
        ),
        read_body="        return len(self._q)\n",  # no lock held
    )
    findings = lint_source(src)
    assert _codes(findings) == ["FF110"]
    assert "outside a `with _lock:` scope" in findings[0].message


def test_locked_suffix_method_exempt():
    src = (
        "import threading\n"
        "class T:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._q = []  # ffcheck: guarded-by=_lock\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop).start()\n"
        "    def _loop(self):\n"
        "        with self._lock:\n"
        "            self._drain_locked()\n"
        "    def _drain_locked(self):\n"
        "        self._q.append(1)\n"
        "    def read(self):\n"
        "        with self._lock:\n"
        "            return len(self._q)\n"
    )
    assert lint_source(src) == []


def test_requires_lock_comment_exempt():
    src = _threaded_class(
        init_extra="        self._q = []  # ffcheck: guarded-by=_lock\n",
        loop_body=(
            "        with self._lock:\n"
            "            self._q.append(1)\n"
        ),
        read_body="        return len(self._q)\n",
    ).replace(
        "    def read(self):",
        "    # ffcheck: requires-lock=_lock\n    def read(self):",
    )
    assert lint_source(src) == []


def test_base_class_registry_binds_for_subclass():
    """A guarded-by comment on a BASE initializer line must register the
    attribute for subclass views too (the Transport hierarchy keeps
    counters on the base, threads on the subclass)."""
    src = (
        "import threading\n"
        "class Base:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0  # ffcheck: guarded-by=_lock\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "class Sub(Base):\n"
        "    def __init__(self):\n"
        "        super().__init__()\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop).start()\n"
        "    def _loop(self):\n"
        "        self.bump()\n"
    )
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# FF111 held-lock-blocking-call

from flexflow_tpu.analysis.rules.held_lock_blocking import (  # noqa: E402
    analyze_lock_order,
    find_order_cycles,
)


def test_blocking_call_under_lock_flagged():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def send(self, sock, data):\n"
        "        with self._lock:\n"
        "            sock.sendall(data)\n"
    )
    findings = lint_source(src)
    assert _codes(findings) == ["FF111"]
    assert "sendall" in findings[0].message


def test_transitively_blocking_callee_flagged():
    src = (
        "import socket\nimport threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def _dial(self):\n"
        "        return socket.create_connection(('h', 1))\n"
        "    def send(self):\n"
        "        with self._lock:\n"
        "            self._dial()\n"
    )
    findings = lint_source(src)
    assert _codes(findings) == ["FF111"]
    assert "blocks transitively" in findings[0].message


def test_blocking_outside_lock_ok():
    src = (
        "def send(sock, data):\n"
        "    sock.sendall(data)\n"
    )
    assert lint_source(src) == []


def test_non_lock_with_scope_ok():
    src = (
        "def f(path, sock):\n"
        "    with open(path) as fh:\n"
        "        sock.sendall(fh.read())\n"
    )
    assert lint_source(src) == []


def test_held_lock_suppression():
    src = (
        "import threading\n"
        "_LOCK = threading.Lock()\n"
        "def f(sock, data):\n"
        "    with _LOCK:\n"
        "        # ffcheck: disable=FF111 -- test fixture\n"
        "        sock.sendall(data)\n"
    )
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# lock-acquisition-order graph


def test_lock_order_inversion_detected():
    src = (
        "import threading\n"
        "A_LOCK = threading.Lock()\n"
        "B_LOCK = threading.Lock()\n"
        "def f():\n"
        "    with A_LOCK:\n"
        "        with B_LOCK:\n"
        "            pass\n"
        "def g():\n"
        "    with B_LOCK:\n"
        "        with A_LOCK:\n"
        "            pass\n"
    )
    edges = analyze_lock_order({"inv.py": src})
    assert ("A_LOCK", "B_LOCK") in edges and ("B_LOCK", "A_LOCK") in edges
    cycles = find_order_cycles(edges)
    assert len(cycles) == 1
    assert set(cycles[0]) == {"A_LOCK", "B_LOCK"}


def test_lock_order_cross_file_dispatch_edge():
    """A call matched by NAME across files pulls the callee's locks
    into the held scope — the loopback-dispatch → server-core pattern."""
    caller = (
        "import threading\n"
        "DISPATCH_LOCK = threading.Lock()\n"
        "def run(core, req):\n"
        "    with DISPATCH_LOCK:\n"
        "        core.dispatch(req)\n"
    )
    callee = (
        "import threading\n"
        "class Core:\n"
        "    def __init__(self):\n"
        "        self._inner_lock = threading.Lock()\n"
        "    def dispatch(self, req):\n"
        "        with self._inner_lock:\n"
        "            return req\n"
    )
    edges = analyze_lock_order({"a.py": caller, "b.py": callee})
    assert ("DISPATCH_LOCK", "Core._inner_lock") in edges
    assert find_order_cycles(edges) == []


def test_repo_lock_order_acyclic_and_expected_edges():
    """The real corpus is acyclic AND contains the two known-good
    ordering edges (writer-lock → stats, loopback-dispatch →
    server-core) — if these vanish, the analysis went blind, not clean."""
    cluster = os.path.join(REPO, "flexflow_tpu", "serve", "cluster")
    paths = [os.path.join(cluster, f)
             for f in ("transport.py", "server.py", "remote.py")]
    sources = {p: open(p).read() for p in paths}
    edges = analyze_lock_order(sources)
    assert find_order_cycles(edges) == []
    assert ("SocketTransport._lock", "_STATS_LOCK") in edges
    assert (
        "_LOOPBACK_DISPATCH_LOCK", "ReplicaServerCore._dispatch_lock"
    ) in edges


# ---------------------------------------------------------------------------
# wire-protocol drift checker

from flexflow_tpu.analysis.protocol import (  # noqa: E402
    SERVER_ONLY_METHODS,
    check_protocol_drift,
    diff_protocol,
    server_dispatch_table,
)

_DRIFT_SERVER = (
    "class ReplicaServerCore:\n"
    "    def _envelope(self, **kw):\n"
    "        return {}\n"
    "    def _m_step(self, args):\n"
    "        return self._envelope(progressed=True)\n"
    "    def _m_submit(self, args):\n"
    "        rid = args['rid']\n"
    "        return {'rid': rid}\n"
    "    def _m_hello(self, args):\n"
    "        return {}\n"
    "    def _m_orphan(self, args):\n"
    "        return {}\n"
)


def test_drift_checker_flags_skew():
    client = (
        "class RemoteReplica:\n"
        "    def a(self):\n"
        "        res = self._rpc('step', {})\n"
        "        return res['missing_key']\n"
        "    def b(self):\n"
        "        return self._rpc('submit', {'wrong': 1})\n"
        "    def c(self):\n"
        "        self._rpc('gone', {})\n"
    )
    problems = "\n".join(
        diff_protocol(_DRIFT_SERVER, {"client.py": client})
    )
    assert "no _m_gone handler" in problems
    assert "omits required arg(s) ['rid']" in problems
    assert "passes arg(s) ['wrong']" in problems
    assert "requires response key(s) ['missing_key']" in problems
    assert "_m_orphan has no client call site" in problems
    # hello is server-only by design: never reported
    assert "_m_hello" not in problems


def test_drift_checker_clean_on_matched_pair():
    client = (
        "class RemoteReplica:\n"
        "    def a(self):\n"
        "        res = self._rpc('step', {})\n"
        "        return res['progressed']\n"
        "    def b(self):\n"
        "        return self._rpc('submit', {'rid': 1})['rid']\n"
        "    def c(self):\n"
        "        self._rpc('orphan', {})\n"
    )
    assert diff_protocol(_DRIFT_SERVER, {"client.py": client}) == []


def test_repo_protocol_drift_clean():
    cluster = os.path.join(REPO, "flexflow_tpu", "serve", "cluster")
    assert check_protocol_drift(
        os.path.join(cluster, "server.py"),
        [os.path.join(cluster, "remote.py")],
    ) == []


def test_dispatch_table_covers_runtime_handlers():
    """Meta-guard for the drift checker itself: the statically scraped
    dispatch table must equal the runtime ``_m_*`` method set of
    ReplicaServerCore — if the AST scrape goes blind (class renamed,
    handlers defined dynamically), this fails before the drift check
    silently passes on an empty table."""
    from flexflow_tpu.serve.cluster.server import ReplicaServerCore

    path = os.path.join(
        REPO, "flexflow_tpu", "serve", "cluster", "server.py"
    )
    table = server_dispatch_table(open(path).read())
    runtime = {
        name[3:] for name in dir(ReplicaServerCore)
        if name.startswith("_m_")
    }
    assert set(table) == runtime and runtime, (set(table), runtime)
    assert SERVER_ONLY_METHODS <= runtime


def test_fixture_corpus_lints_clean():
    """The premerge-gate-16 fixture corpus (tests/fixtures/ffcheck/)
    exercises every FF110 registry form and FF109/FF111 suppression —
    a suppression-parser or registry regression surfaces here first."""
    fixtures = os.path.join(REPO, "tests", "fixtures", "ffcheck")
    findings = lint_paths([fixtures])
    assert not findings, "\n".join(f.format() for f in findings)
