"""mxlint — repo-wide AST lint for compiled-path hazards.

The jaxpr auditor (mxnet_tpu/analysis) checks programs that already
traced; mxlint catches the bug classes that live in the SOURCE and
only manifest as runtime symptoms the diagnostics layer counts after
the fact (recompile storms, config typos, hot-loop host syncs):

  MXL001 unregistered-env      read of a ``MXNET_*`` env var not
                               declared in mxnet_tpu/env.py — a typo'd
                               knob silently running on defaults
  MXL002 direct-env-read       ``MXNET_*`` read bypassing the
                               mxnet_tpu.env accessors (parsing/
                               truthiness drift between sites)
  MXL003 recompile-hazard      time/random/uuid call inside a traced
                               function: the value bakes into the
                               trace as a constant — every step gets
                               yesterday's timestamp, or the jit
                               retraces forever (the storms
                               diagnostics.recompile_stats() counts
                               after the fact)
  MXL004 host-sync-in-loop     ``.block_until_ready()`` / ``.item()``
                               / ``np.asarray`` / ``float()`` on
                               device values inside a loop: one
                               device->host sync per iteration
  MXL005 import-time-env-read  module-level env read: launchers that
                               inject env per worker after import are
                               silently ignored (knobs registered
                               ``import_time=True`` in env.py are
                               exempt — that contract is documented)
  MXL006 bare-except-collective  ``except:`` around a collective call
                               site: swallows the desync/timeout the
                               flight recorder needs to see (also
                               catches KeyboardInterrupt/SystemExit)
  MXL008 ad-hoc-exit-code      ``os._exit``/``sys.exit`` with a bare
                               nonzero NUMERIC LITERAL outside the
                               sanctioned exit-code sites
                               (diagnostics.py / elastic/ / serving/):
                               the exit-code taxonomy (83 preempted,
                               84 diverged, 85 watchdog-abort, 86
                               restart-budget, 87 sdc, 137 killed) is
                               LOAD-BEARING for the elastic
                               supervisor's failure classification —
                               a new code invented ad hoc silently
                               lands in the "crashed" bucket (or
                               worse, collides).  Exit through the
                               named constants (EXIT_*,
                               KILL_EXIT_CODE) or add the code to the
                               taxonomy first.
  MXL007 jax-in-decode-worker  jax/device call (``device_put``,
                               ``block_until_ready``, any ``jax.*``)
                               inside a decode-worker function: pool
                               workers are HOST-ONLY — under the
                               default fork start method a worker
                               touching the parent's initialized jax
                               runtime deadlocks, and device placement
                               belongs to the async device stage
                               (io_pipeline.py).  Worker functions are
                               those named ``*_worker_main`` /
                               ``*decode_worker*`` / ``*io_worker*``
                               and functions passed as ``iter_fn`` to
                               InputPipeline/ShardedDecodePool.
  MXL009 rogue-device-trace    direct ``jax.profiler.start_trace`` /
                               ``stop_trace`` / ``trace`` /
                               ``TraceAnnotation`` /
                               ``StepTraceAnnotation`` outside
                               mxnet_tpu/traceview/capture.py: the
                               capture wrapper is the ONE sanctioned
                               XLA device-trace site — a second trace
                               session corrupts (or silently drops)
                               the armed capture, and ad-hoc
                               annotations bypass the step-window
                               naming the attribution walker keys on.
  MXL010 wallclock-in-serving  ``time.time()`` (or ``datetime.now``)
                               inside ``mxnet_tpu/serving/``: every
                               serving deadline, duration, and
                               reqtrace span is monotonic-clock by
                               contract — one wall-clock read mixed in
                               makes a deadline jump on NTP slew and
                               an autopsy attribute negative time.
                               ``time.monotonic()`` (or
                               ``perf_counter``) is required;
                               wall-clock is allowed only for dump/
                               artifact timestamps via an inline
                               ``# mxlint: disable=MXL010``.

Pure-AST: imports NOTHING from the package (the env registry is read
by parsing mxnet_tpu/env.py's ``register(...)`` calls), so it lints a
broken tree too.  Suppress one line with ``# mxlint: disable=MXL00X``
(or ``# noqa: MXL00X``); accept legacy findings in
``tools/mxlint_baseline.json``.  Exit 0 = clean (new findings only),
1 = new findings, 2 = usage error.

Run: ``python -m tools.mxlint [--json out.json] [paths...]``
      ``python -m tools.mxlint --self-test``
"""
from __future__ import annotations

import argparse
import ast
import hashlib
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV_PY = os.path.join(REPO, "mxnet_tpu", "env.py")
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "mxlint_baseline.json")
DEFAULT_TARGETS = ("mxnet_tpu",)

MXNET_RE = re.compile(r"^MXNET_[A-Z0-9_]+$")

CODES = {
    "MXL001": "unregistered MXNET_* env var (declare it in mxnet_tpu/env.py)",
    "MXL002": "MXNET_* env read bypasses the mxnet_tpu.env accessors",
    "MXL003": "recompile hazard: host-varying call inside a traced function",
    "MXL004": "host sync inside a loop body",
    "MXL005": "import-time env read (launcher env injection ignored)",
    "MXL006": "bare except around a collective call site",
    "MXL007": "jax/device call inside a decode-worker function "
              "(workers are host-only; the device stage owns placement)",
    "MXL008": "numeric-literal exit code outside the sanctioned exit "
              "sites (the 83-87/137 taxonomy is load-bearing for the "
              "supervisor — exit through the named constants)",
    "MXL009": "direct jax.profiler trace call outside "
              "mxnet_tpu/traceview/capture.py (the one sanctioned "
              "device-trace capture site)",
    "MXL010": "wall-clock read in the serving tier (deadlines/"
              "durations are monotonic-clock by contract; "
              "time.monotonic() required — inline-disable only for "
              "dump timestamps)",
}

# the serving tier's clock discipline (MXL010): every deadline and
# duration is monotonic; wall-clock only via inline disable
SERVING_TIER_RE = re.compile(r"mxnet_tpu[/\\]serving[/\\]")
WALLCLOCK_CALLS = {("time", "time"), ("time", "time_ns"),
                   ("datetime", "now"), ("datetime", "utcnow")}

# files whose exit codes ARE the taxonomy: the documented contract
# lives there, everything else must exit through its named constants
SANCTIONED_EXIT_RE = re.compile(
    r"mxnet_tpu[/\\](diagnostics\.py$|elastic[/\\]|serving[/\\])")

# the ONE sanctioned jax.profiler device-trace site (MXL009)
SANCTIONED_TRACE_RE = re.compile(
    r"mxnet_tpu[/\\]traceview[/\\]capture\.py$")
# jax.profiler attributes that open/annotate an XLA device trace
TRACE_PROFILER_ATTRS = {"start_trace", "stop_trace", "trace",
                        "TraceAnnotation", "StepTraceAnnotation"}

# decode-worker entry points by naming convention
WORKER_NAME_RE = re.compile(r"(_worker_main$|decode_worker|io_worker)")
# pool constructors whose iter_fn argument runs inside workers
WORKER_POOL_CTORS = {"InputPipeline", "ShardedDecodePool"}
# calls that flag MXL007 inside a worker function
WORKER_FORBIDDEN_ATTRS = {"device_put", "block_until_ready"}
WORKER_FORBIDDEN_ROOTS = {"jax", "jnp"}

# functions whose callable argument is traced by jax
TRACE_ENTRY_ATTRS = {
    "jit", "shard_map", "checkpoint", "remat", "vjp", "value_and_grad",
    "grad", "scan", "while_loop", "cond", "pmap", "custom_vjp",
    "make_jaxpr",
}
# env-reading callables (attribute names)
ENV_READ_ATTRS = {
    "get", "getenv", "get_raw", "get_str", "get_int", "get_float",
    "get_bool", "env_int", "env_bool", "_env_int", "_env_float",
}
# receivers that mark an env accessor call as routed through the registry
ENV_MODULE_NAMES = {"env", "_env", "_envmod"}

HOST_VARYING = {
    ("time", "time"), ("time", "perf_counter"), ("time", "monotonic"),
    ("time", "time_ns"), ("time", "perf_counter_ns"),
    ("datetime", "now"), ("datetime", "utcnow"),
    ("os", "urandom"), ("uuid", "uuid4"), ("uuid", "uuid1"),
}
RANDOM_MODULES = {"random"}          # python's random.*; np.random.*
HOST_SYNC_ATTRS = {"block_until_ready", "item"}
HOST_SYNC_NP_FUNCS = {"asarray", "array"}
COLLECTIVE_TOKENS = {
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "push", "pull",
    "allreduce", "broadcast", "bucketed_reduce", "ring_allreduce_flat",
}


class LintFinding(dict):
    @property
    def fingerprint(self) -> str:
        # stable across line moves: file + code + enclosing scope +
        # normalized source snippet
        tag = "%s::%s::%s::%s" % (
            self["file"], self["code"], self["scope"],
            hashlib.sha1(self["snippet"].encode()).hexdigest()[:12])
        return tag


def registered_env_names(env_path: str = ENV_PY
                         ) -> Tuple[Set[str], Set[str]]:
    """(registered, import_time_ok) MXNET_* names, parsed statically
    from env.py's register(...) calls."""
    registered: Set[str] = set()
    import_ok: Set[str] = set()
    try:
        tree = ast.parse(open(env_path).read(), env_path)
    except (OSError, SyntaxError):
        return registered, import_ok
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "register" and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        registered.add(first.value)
        for kw in node.keywords:
            if kw.arg == "import_time" and isinstance(kw.value,
                                                     ast.Constant) \
                    and kw.value.value:
                import_ok.add(first.value)
    return registered, import_ok


def _dotted(node: ast.AST) -> List[str]:
    """['np', 'random', 'normal'] for np.random.normal; [] if not a
    plain name/attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return []


def _name_nodes(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class ModuleLinter:
    def __init__(self, path: str, source: str, registered: Set[str],
                 import_ok: Set[str], is_env_py: bool):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.registered = registered
        self.import_ok = import_ok
        self.is_env_py = is_env_py
        self.findings: List[LintFinding] = []
        self.tree = ast.parse(source, path)
        self.traced_fns = self._collect_traced_fns()
        self.worker_fns = self._collect_worker_fns()
        self.sanctioned_exit = bool(
            SANCTIONED_EXIT_RE.search(os.path.abspath(path)))
        self.sanctioned_trace = bool(
            SANCTIONED_TRACE_RE.search(os.path.abspath(path)))
        self.serving_tier = bool(
            SERVING_TIER_RE.search(os.path.abspath(path)))

    # -- pass 1: which local functions get traced by jax? --------------
    def _collect_traced_fns(self) -> Set[str]:
        defined = {n.name for n in ast.walk(self.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        traced: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                chain = _dotted(node.func)
                if chain and chain[-1] in TRACE_ENTRY_ATTRS:
                    for arg in node.args:
                        traced |= _name_nodes(arg) & defined
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    tokens = set(_dotted(dec)) if not isinstance(
                        dec, ast.Call) else set(_dotted(dec.func))
                    if isinstance(dec, ast.Call):
                        for a in ast.walk(dec):
                            tokens |= set(_dotted(a) if isinstance(
                                a, (ast.Attribute, ast.Name)) else [])
                    if tokens & TRACE_ENTRY_ATTRS:
                        traced.add(node.name)
        return traced

    # -- pass 1b: which local functions run inside decode workers? -----
    def _collect_worker_fns(self) -> Set[str]:
        defined = {n.name for n in ast.walk(self.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        workers = {n for n in defined if WORKER_NAME_RE.search(n)}
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _dotted(node.func)
            if not chain or chain[-1] not in WORKER_POOL_CTORS:
                continue
            cands = list(node.args[:1]) + \
                [kw.value for kw in node.keywords if kw.arg == "iter_fn"]
            for arg in cands:
                workers |= _name_nodes(arg) & defined
        return workers

    # -- helpers -------------------------------------------------------
    def _suppressed(self, line: int, code: str) -> bool:
        if 1 <= line <= len(self.lines):
            text = self.lines[line - 1]
            m = re.search(r"#\s*(?:mxlint:\s*disable=|noqa:\s*)"
                          r"([A-Z0-9, ]+)", text)
            if m and code in m.group(1):
                return True
        return False

    def _add(self, node: ast.AST, code: str, message: str,
             scope: str) -> None:
        line = getattr(node, "lineno", 0)
        if self._suppressed(line, code):
            return
        try:
            snippet = ast.get_source_segment(self.source, node) or ""
        except Exception:
            snippet = ""
        snippet = " ".join(snippet.split())[:160]
        self.findings.append(LintFinding(
            file=os.path.relpath(self.path, REPO), line=line, code=code,
            scope=scope, message=message, snippet=snippet))

    # -- pass 2: walk with context -------------------------------------
    def run(self) -> List[LintFinding]:
        self._walk(self.tree, fn_stack=[], traced=False, loop_depth=0)
        return self.findings

    def _env_name_in_call(self, call: ast.Call) -> Optional[str]:
        for arg in call.args[:1]:
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                            str) \
                    and MXNET_RE.match(arg.value):
                return arg.value
        return None

    def _check_env_read(self, node: ast.AST, fn_stack: List[str]
                        ) -> None:
        """MXL001/002/005 on one potential env-read node."""
        scope = ".".join(fn_stack) or "<module>"
        name = None
        routed = False
        if isinstance(node, ast.Call):
            chain = _dotted(node.func)
            if not chain or chain[-1] not in ENV_READ_ATTRS:
                return
            name = self._env_name_in_call(node)
            routed = len(chain) >= 2 and chain[-2] in ENV_MODULE_NAMES
        elif isinstance(node, ast.Subscript):
            chain = _dotted(node.value)
            if chain[-1:] != ["environ"]:
                return
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str) \
                    and MXNET_RE.match(sl.value):
                name = sl.value
        if name is None:
            return
        if name not in self.registered:
            self._add(node, "MXL001",
                      "read of unregistered env var %s" % name, scope)
        if not routed and not self.is_env_py:
            self._add(node, "MXL002",
                      "%s read via os.environ — route through "
                      "mxnet_tpu.env accessors" % name, scope)
        if not fn_stack and not self.is_env_py \
                and name not in self.import_ok:
            self._add(node, "MXL005",
                      "%s read at import time — read lazily or "
                      "register import_time=True with justification"
                      % name, scope)

    def _check_traced_call(self, node: ast.Call, fn_stack: List[str]
                           ) -> None:
        chain = _dotted(node.func)
        if len(chain) < 2:
            return
        scope = ".".join(fn_stack)
        pair = (chain[-2], chain[-1])
        if pair in HOST_VARYING or chain[0] in RANDOM_MODULES \
                or (len(chain) >= 3 and chain[-2] == "random"
                    and chain[0] in ("np", "numpy")):
            self._add(node, "MXL003",
                      "%s inside traced function %r: value is baked "
                      "into the trace as a constant (or forces a "
                      "retrace per call)" % (".".join(chain), scope),
                      scope)

    def _check_host_sync(self, node: ast.Call, fn_stack: List[str]
                         ) -> None:
        scope = ".".join(fn_stack) or "<module>"
        chain = _dotted(node.func)
        if not chain:
            return
        if chain[-1] in HOST_SYNC_ATTRS:
            self._add(node, "MXL004",
                      ".%s() inside a loop: one device->host sync per "
                      "iteration" % chain[-1], scope)
        elif len(chain) >= 2 and chain[0] in ("np", "numpy") \
                and chain[-1] in HOST_SYNC_NP_FUNCS:
            self._add(node, "MXL004",
                      "np.%s inside a loop: device->host transfer per "
                      "iteration" % chain[-1], scope)

    def _check_worker_call(self, node: ast.Call, fn_stack: List[str]
                           ) -> None:
        """MXL007: jax/device calls under a decode-worker function."""
        chain = _dotted(node.func)
        if not chain:
            return
        if chain[-1] in WORKER_FORBIDDEN_ATTRS \
                or chain[0] in WORKER_FORBIDDEN_ROOTS:
            self._add(node, "MXL007",
                      "%s inside decode-worker function %r — workers "
                      "are host-only (fork-safety + the device stage "
                      "owns placement)"
                      % (".".join(chain), ".".join(fn_stack)),
                      ".".join(fn_stack))

    def _check_exit_call(self, node: ast.Call, fn_stack: List[str]
                         ) -> None:
        """MXL008: ``os._exit(<literal>)``/``sys.exit(<literal>)`` with
        a nonzero int outside the sanctioned exit-code sites.  Named
        constants (EXIT_PREEMPTED, KILL_EXIT_CODE, ...) and
        ``sys.exit(main())`` pass — the point is that new CODES enter
        the taxonomy deliberately, not that exits are forbidden."""
        if self.sanctioned_exit:
            return
        chain = _dotted(node.func)
        if chain[-2:] not in (["os", "_exit"], ["sys", "exit"]):
            return
        if not node.args:
            return
        a = node.args[0]
        if isinstance(a, ast.Constant) and isinstance(a.value, int) \
                and not isinstance(a.value, bool) and a.value != 0:
            self._add(node, "MXL008",
                      "%s(%d): numeric-literal exit code outside the "
                      "sanctioned sites — the 83-87/137 taxonomy "
                      "drives the elastic supervisor; exit through a "
                      "named constant" % (".".join(chain), a.value),
                      ".".join(fn_stack) or "<module>")

    def _check_trace_call(self, node: ast.Call, fn_stack: List[str]
                          ) -> None:
        """MXL009: ``jax.profiler.start_trace/stop_trace/trace/
        TraceAnnotation/StepTraceAnnotation`` outside
        mxnet_tpu/traceview/capture.py.  The wrappers there are the
        one sanctioned device-trace site — route through
        ``traceview.capture`` (``profiler.span`` does, for every
        ``mx.*`` span) so a second profiler session can never corrupt
        an armed capture."""
        if self.sanctioned_trace:
            return
        chain = _dotted(node.func)
        if len(chain) < 3 or chain[-3] != "jax" \
                or chain[-2] != "profiler" \
                or chain[-1] not in TRACE_PROFILER_ATTRS:
            return
        self._add(node, "MXL009",
                  "%s: direct jax.profiler trace call outside "
                  "mxnet_tpu/traceview/capture.py — route through "
                  "traceview.capture (the one sanctioned device-trace "
                  "site)" % ".".join(chain),
                  ".".join(fn_stack) or "<module>")

    def _check_wallclock_call(self, node: ast.Call,
                              fn_stack: List[str]) -> None:
        """MXL010: wall-clock reads in mxnet_tpu/serving/.  A deadline
        computed from ``time.time()`` jumps under NTP slew and cannot
        be compared against the monotonic enqueue/done stamps the rest
        of the tier records."""
        if not self.serving_tier:
            return
        chain = _dotted(node.func)
        if tuple(chain[-2:]) not in WALLCLOCK_CALLS:
            return
        self._add(node, "MXL010",
                  "%s() in the serving tier — deadlines/durations are "
                  "monotonic-clock by contract; use time.monotonic() "
                  "(inline-disable only for dump timestamps)"
                  % ".".join(chain),
                  ".".join(fn_stack) or "<module>")

    def _check_bare_except(self, node: ast.Try, fn_stack: List[str]
                           ) -> None:
        scope = ".".join(fn_stack) or "<module>"
        bare = [h for h in node.handlers if h.type is None]
        if not bare:
            return
        tokens: Set[str] = set()
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    chain = _dotted(sub.func)
                    if chain:
                        tokens.add(chain[-1])
        if tokens & COLLECTIVE_TOKENS:
            self._add(bare[0], "MXL006",
                      "bare `except:` around collective call(s) %s — "
                      "swallows the desync/timeout evidence (and "
                      "KeyboardInterrupt)"
                      % sorted(tokens & COLLECTIVE_TOKENS), scope)

    def _walk(self, node: ast.AST, fn_stack: List[str], traced: bool,
              loop_depth: int, worker: bool = False) -> None:
        for child in ast.iter_child_nodes(node):
            c_stack, c_traced, c_loop = fn_stack, traced, loop_depth
            c_worker = worker
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                c_stack = fn_stack + [child.name]
                c_traced = traced or child.name in self.traced_fns
                # nested defs inherit worker scope: they run in-process
                c_worker = worker or child.name in self.worker_fns
                c_loop = 0  # a new function body is a new loop scope
            elif isinstance(child, (ast.For, ast.While)):
                c_loop = loop_depth + 1
            if isinstance(child, (ast.Call, ast.Subscript)):
                self._check_env_read(child, fn_stack)
            if isinstance(child, ast.Call):
                if traced:
                    self._check_traced_call(child, fn_stack)
                if loop_depth > 0 and not traced:
                    self._check_host_sync(child, fn_stack)
                if worker:
                    self._check_worker_call(child, fn_stack)
                self._check_exit_call(child, fn_stack)
                self._check_trace_call(child, fn_stack)
                self._check_wallclock_call(child, fn_stack)
            if isinstance(child, ast.Try):
                self._check_bare_except(child, fn_stack)
            self._walk(child, c_stack, c_traced, c_loop, c_worker)


def lint_paths(paths: Sequence[str], registered: Set[str],
               import_ok: Set[str]) -> List[LintFinding]:
    findings: List[LintFinding] = []
    files: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
        else:
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                files += [os.path.join(root, n) for n in sorted(names)
                          if n.endswith(".py")]
    for path in sorted(files):
        try:
            src = open(path).read()
        except OSError:
            continue
        is_env_py = os.path.abspath(path) == os.path.abspath(ENV_PY)
        try:
            linter = ModuleLinter(path, src, registered, import_ok,
                                  is_env_py)
        except SyntaxError as exc:
            findings.append(LintFinding(
                file=os.path.relpath(path, REPO),
                line=getattr(exc, "lineno", 0) or 0, code="MXL000",
                scope="<module>", message="syntax error: %s" % exc,
                snippet=""))
            continue
        findings += linter.run()
    return findings


def load_baseline(path: str) -> Set[str]:
    try:
        with open(path) as f:
            return set(json.load(f).get("fingerprints", []))
    except (OSError, ValueError):
        return set()


# ---------------------------------------------------------------------------
SELF_TEST_SRC = '''
import os, sys, time, random
import numpy as np
import jax

K = os.environ.get("MXNET_NOT_A_REAL_KNOB", "0")          # 001/002/005

def build():
    cap = int(os.environ.get("MXNET_KVSTORE_BUCKET_BYTES", 4))  # 002

    def step(x):
        seed = time.time()                                 # 003
        noise = random.random()                            # 003
        return x * seed + noise

    return jax.jit(step)

def drain(vals):
    out = []
    for v in vals:
        out.append(np.asarray(v))                          # 004
        v.block_until_ready()                              # 004
    return out

def reduce_all(x):
    try:
        return jax.lax.psum(x, "dp")
    except:                                                # 006
        return x

def _decode_worker_main(q):
    x = q.get()
    jax.device_put(x)                                      # 007
    x.block_until_ready()                                  # 007

def my_iter_factory(num_parts=1, part_index=0):
    import jax.numpy as jnp
    return jnp.zeros(())                                   # 007 (iter_fn)

def start_pool():
    return InputPipeline(my_iter_factory, num_workers=2)

def give_up():
    sys.exit(86)                                           # 008

def rogue_trace(d):
    jax.profiler.start_trace(d)                            # 009
EXIT_CUSTOM = 99
def die_hard(ok):
    if ok:
        sys.exit(0)           # literal 0 is fine (success)
    if os.environ.get("X"):
        sys.exit(EXIT_CUSTOM)  # named constant: deliberate taxonomy
    os._exit(87)                                           # 008
'''

EXPECT_SELF_TEST = {"MXL001": 1, "MXL002": 2, "MXL003": 2, "MXL004": 2,
                    "MXL005": 1, "MXL006": 1, "MXL007": 3, "MXL008": 2,
                    "MXL009": 1}

# MXL010 is path-gated to mxnet_tpu/serving/ — its fixture lints under
# a serving-tier path (the main fixture stays outside, so the counts
# above are unaffected)
SERVING_SELF_TEST_SRC = '''
import time

def offer(req, deadline_s):
    t0 = time.time()                                       # 010
    req.deadline = time.time() + deadline_s                # 010
    ok = time.monotonic() - t0
    stamp = time.time()  # mxlint: disable=MXL010
    return ok, stamp
'''

EXPECT_SERVING_SELF_TEST = {"MXL010": 2}


def self_test() -> int:
    registered, import_ok = registered_env_names()
    if not registered:
        print("mxlint self-test FAILED: no names parsed from env.py")
        return 1
    if "MXNET_KVSTORE_BUCKET_BYTES" not in registered:
        print("mxlint self-test FAILED: registry parse missed a knob")
        return 1
    linter = ModuleLinter("<selftest>.py", SELF_TEST_SRC, registered,
                          import_ok, is_env_py=False)
    counts: Dict[str, int] = {}
    for f in linter.run():
        counts[f["code"]] = counts.get(f["code"], 0) + 1
    bad = {c: (counts.get(c, 0), want)
           for c, want in EXPECT_SELF_TEST.items()
           if counts.get(c, 0) != want}
    if bad:
        print("mxlint self-test FAILED: got!=want per code:", bad,
              "all:", counts)
        return 1
    if counts.get("MXL010"):
        print("mxlint self-test FAILED: MXL010 fired outside "
              "mxnet_tpu/serving/ (path gate broken):", counts)
        return 1
    sv = ModuleLinter("mxnet_tpu/serving/<selftest>.py",
                      SERVING_SELF_TEST_SRC, registered, import_ok,
                      is_env_py=False)
    sv_counts: Dict[str, int] = {}
    for f in sv.run():
        sv_counts[f["code"]] = sv_counts.get(f["code"], 0) + 1
    if sv_counts != EXPECT_SERVING_SELF_TEST:
        print("mxlint self-test FAILED: serving-tier fixture "
              "got!=want:", sv_counts, "want:",
              EXPECT_SERVING_SELF_TEST)
        return 1
    n_seed = sum(EXPECT_SELF_TEST.values()) + \
        sum(EXPECT_SERVING_SELF_TEST.values())
    n_codes = len(EXPECT_SELF_TEST) + len(EXPECT_SERVING_SELF_TEST)
    print("mxlint self-test OK: %d seeded findings across %d codes, "
          "%d env vars in registry" % (n_seed, n_codes,
                                       len(registered)))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.mxlint",
        description="AST lint for compiled-path hazards (see module "
                    "docstring for codes)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: mxnet_tpu/)")
    ap.add_argument("--json", help="write findings JSON here")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept every current finding into the "
                         "baseline file (review the diff!)")
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()

    registered, import_ok = registered_env_names()
    paths = args.paths or [os.path.join(REPO, t)
                           for t in DEFAULT_TARGETS]
    findings = lint_paths(paths, registered, import_ok)
    if args.update_baseline:
        with open(args.baseline, "w") as fh:
            json.dump({"note": "accepted mxlint findings; regenerate "
                               "with --update-baseline and review",
                       "fingerprints": sorted(
                           {f.fingerprint for f in findings})}, fh,
                      indent=1)
            fh.write("\n")
        print("mxlint: baseline updated with %d fingerprint(s) -> %s"
              % (len(findings), args.baseline))
        return 0
    baseline = set() if args.no_baseline else load_baseline(
        args.baseline)
    new = [f for f in findings if f.fingerprint not in baseline]
    suppressed = len(findings) - len(new)
    for f in sorted(new, key=lambda f: (f["file"], f["line"])):
        print("%s:%d %s %s  [%s]" % (f["file"], f["line"], f["code"],
                                     f["message"], f["scope"]))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"n_findings": len(new),
                       "n_suppressed": suppressed,
                       "findings": [dict(f, fingerprint=f.fingerprint)
                                    for f in new]}, fh, indent=1)
    print("mxlint: %d new finding(s), %d baseline-suppressed, "
          "%d file(s) with findings" % (len(new), suppressed,
                                        len({f['file'] for f in findings})
                                        if findings else 0))
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
