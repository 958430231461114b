"""mxlint (tools/analysis): per-rule fixtures + the tier-1 self-check gate.

Every rule family gets a known-bad snippet (must fire), a known-clean
snippet (must stay silent), and a suppression case (inline disable with
justification must be honored; without justification it must not be).
The gate test at the bottom is the CI contract of ISSUE 3: the shipped
``mxnet_tpu/`` tree has zero unsuppressed findings, so any future PR
that introduces a host sync inside a jitted path, an unlocked
producer-thread attribute, a donated-buffer reuse, or a registry/docs
inconsistency fails tier-1.

Fixtures run the analyzer through its API on temp files — nothing is
imported or executed, mxlint is pure ``ast``.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analysis import (BAD_SUPPRESSION, Config, analyze,  # noqa: E402
                            default_rules, exit_code)

pytestmark = pytest.mark.mxlint


def lint(tmp_path, source, name="snippet.py", config=None):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return analyze([p], config=config, root=tmp_path)


def fired(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def suppressed(findings, rule):
    return [f for f in findings if f.rule == rule and f.suppressed]


# ---------------------------------------------------------------------------
# trace-safety family
# ---------------------------------------------------------------------------

def test_trace_host_sync_bad(tmp_path):
    fs = lint(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x, y):
            a = float(x)
            b = x.item()
            c = np.asarray(y)
            print("dbg", a)
            return a + b + c
        """)
    msgs = fired(fs, "trace-host-sync")
    assert len(msgs) == 4, [f.message for f in fs]


def test_trace_host_sync_clean(tmp_path):
    # metadata reads, statics, and device-side math are all fine
    fs = lint(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, y):
            n = float(x.shape[0])        # shape is static under trace
            scale = int(len(y.shape))
            return jnp.mean(x) * n + scale
        """)
    assert not fired(fs, "trace-host-sync")


def test_trace_host_sync_through_compile_sinks(tmp_path):
    # a loss_fn handed to TrainStep is traced by the fused step
    fs = lint(tmp_path, """
        def loss_fn(out, label):
            return float(out) - label

        def build(net, opt):
            from mxnet_tpu import parallel
            return parallel.TrainStep(net, loss_fn, opt)
        """)
    assert len(fired(fs, "trace-host-sync")) == 1


def test_trace_host_sync_suppression(tmp_path):
    fs = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # mxlint: disable=trace-host-sync -- fixture: intentional verdict read
        """)
    assert not fired(fs, "trace-host-sync")
    sup = suppressed(fs, "trace-host-sync")
    assert len(sup) == 1 and "intentional" in sup[0].justification


def test_trace_python_branch(tmp_path):
    fs = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x, flag):
            if x > 0:                  # BAD: traced value
                x = -x
            while x.sum() < 1:         # BAD
                x = x * 2
            y = 1 if x else 0          # BAD (ternary)
            return x + y

        @jax.jit
        def g(x, xs):
            if x is None:              # identity: static, fine
                return 0
            if isinstance(x, tuple):   # python-type check: fine
                return 1
            if x.ndim == 3:            # metadata: fine
                return 2
            for item in xs:            # iteration is structural: fine
                x = x + item
            return x
        """)
    assert len(fired(fs, "trace-python-branch")) == 3, \
        [f.message for f in fired(fs, "trace-python-branch")]


def test_trace_static_args_not_tainted(tmp_path):
    # static_argnums / partial-bound kernel params are concrete values
    fs = lint(tmp_path, """
        import functools
        import jax

        def body(arrays, key, training, tree):
            if training:               # static_argnums position: fine
                return arrays
            return arrays

        jitted = jax.jit(body, static_argnums=(2, 3))

        @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
        def op(x, mode):
            if mode == "fast":         # nondiff arg: fine
                return x
            return x * 2

        op.defvjp(lambda x, m: (x, None), lambda m, r, g: (g,))
        """)
    assert not fired(fs, "trace-python-branch")


def test_trace_mutable_global(tmp_path):
    fs = lint(tmp_path, """
        import jax

        _CACHE = {}
        _COUNT = 0

        @jax.jit
        def f(x):
            global _COUNT
            _COUNT += 1                # BAD x2 (global stmt + mutation)
            _CACHE["last"] = x         # BAD
            local = {}
            local["fine"] = x          # local dict: fine
            return x
        """)
    assert len(fired(fs, "trace-mutable-global")) == 3


def test_trace_unhashable_static(tmp_path):
    fs = lint(tmp_path, """
        import functools
        import jax

        f = jax.jit(lambda x, opts: x, static_argnames=("opts",))
        g = jax.jit(lambda x, mode: x, static_argnums=(1,))

        @functools.lru_cache(maxsize=64)
        def cached(key):
            return key

        def bad(x):
            a = f(x, opts=[1, 2])      # BAD: list for static kwarg
            b = g(x, [3, 4])           # BAD: list at static position
            c = cached({"k": 1})       # BAD: dict into lru_cache
            return a, b, c

        def clean(x):
            a = f(x, opts=(1, 2))
            b = g(x, "mode")
            c = cached(("k", 1))
            return a, b, c
        """)
    assert len(fired(fs, "trace-unhashable-static")) == 3


# ---------------------------------------------------------------------------
# thread-safety
# ---------------------------------------------------------------------------

_THREAD_BAD = """
    import threading
    import queue

    class Feed:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue(4)
            self.count = 0
            self._t = threading.Thread(target=self._produce)

        def _produce(self):
            while True:
                self.count += 1          # producer write
                self._q.put(self.count)

        def read(self):
            return self.count            # BAD: no lock
"""


def test_thread_unlocked_attr_bad(tmp_path):
    fs = lint(tmp_path, _THREAD_BAD)
    hits = fired(fs, "thread-unlocked-attr")
    assert len(hits) == 1 and "read" in hits[0].message


def test_thread_unlocked_attr_clean(tmp_path):
    fs = lint(tmp_path, """
        import threading
        import queue

        class Feed:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(4)
                self.count = 0
                self._t = threading.Thread(target=self._produce)

            def _produce(self):
                with self._lock:
                    self.count += 1
                self._q.put(1)

            def read(self):
                with self._lock:         # locked: fine
                    return self.count

            def drain(self):
                return self._q.get()     # queue channel: fine
        """)
    assert not fired(fs, "thread-unlocked-attr")


def test_thread_unlocked_attr_helper_runs_on_producer(tmp_path):
    # a helper the thread target calls is producer-side too
    fs = lint(tmp_path, """
        import threading

        class Feed:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0
                self._t = threading.Thread(target=self._produce)

            def _produce(self):
                self._bump()

            def _bump(self):
                self.depth += 1

            def status(self):
                return self.depth        # BAD: helper wrote it unlocked
        """)
    assert len(fired(fs, "thread-unlocked-attr")) == 1


def test_thread_unlocked_attr_suppression(tmp_path):
    src = _THREAD_BAD.replace(
        "return self.count            # BAD: no lock",
        "return self.count  "
        "# mxlint: disable=thread-unlocked-attr -- fixture: monotonic "
        "int, torn reads acceptable")
    fs = lint(tmp_path, src)
    assert not fired(fs, "thread-unlocked-attr")
    assert len(suppressed(fs, "thread-unlocked-attr")) == 1


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

def test_donated_batch_reuse_bad(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def train(feed, net, loss, opt):
            from mxnet_tpu import parallel
            step = parallel.TrainStep(net, loss, opt, donate_batch=True)
            for data, label in feed:
                l = step(data, label)
                total = data.sum()       # BAD: donated buffer
            return l

        def low_level(x):
            g = jax.jit(lambda a: a + 1, donate_argnums=(0,))
            y = g(x)
            return x * y                 # BAD: x was donated
        """)
    assert len(fired(fs, "donated-batch-reuse")) == 2


def test_donated_batch_reuse_clean(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def train(feed, net, loss, opt):
            from mxnet_tpu import parallel
            step = parallel.TrainStep(net, loss, opt, donate_batch=True)
            plain = parallel.TrainStep(net, loss, opt)
            out = []
            for data, label in feed:
                out.append(step(data, label))
                data = None              # re-bound: fine
                label = None
            for data2, label2 in feed:
                out.append(plain(data2, label2))
                keep = label2.sum()      # plain step does not donate
            return out, keep

        def low_level(x):
            g = jax.jit(lambda a: a + 1, donate_argnums=(0,))
            before = x.sum()             # use BEFORE donation: fine
            x = g(x)                     # rebinding through the call
            return before + x
        """)
    assert not fired(fs, "donated-batch-reuse")


# ---------------------------------------------------------------------------
# interprocedural taint (the PR 3 single-hop blind spot, closed)
# ---------------------------------------------------------------------------

def test_taint_crosses_self_helper_call(tmp_path):
    """Regression for the known single-hop blind spot: a host sync in a
    ``self._helper`` the jitted method calls with a traced value was
    invisible to the first-order walk.  The dataflow engine seeds the
    helper's matching parameter and finds it."""
    fs = lint(tmp_path, """
        import jax

        class Model:
            @jax.jit
            def forward(self, x):
                return self._helper(x)

            def _helper(self, v):
                return float(v)          # BAD: traced via forward

            def untraced(self):
                return float(3.0)        # plain python: fine
        """)
    hits = fired(fs, "trace-host-sync")
    assert len(hits) == 1, [f.message for f in fs]
    assert "_helper" in hits[0].message and "traced via" in hits[0].message


def test_taint_crosses_module_helper_two_levels(tmp_path):
    # helper-of-helper is still seen (bounded two-level inlining);
    # untainted arguments stay concrete
    fs = lint(tmp_path, """
        import jax

        def second(w):
            return w.item()              # BAD: two hops from the jit

        def first(v, mode):
            if mode == "x":              # mode untainted: fine
                return second(v)
            return v

        @jax.jit
        def f(x):
            return first(x, "x")
        """)
    assert len(fired(fs, "trace-host-sync")) == 1
    assert not fired(fs, "trace-python-branch")


def test_taint_helper_suppression_still_works(tmp_path):
    fs = lint(tmp_path, """
        import jax

        class Model:
            @jax.jit
            def forward(self, x):
                return self._helper(x)

            def _helper(self, v):
                return float(v)  # mxlint: disable=trace-host-sync -- fixture: verdict read
        """)
    assert not fired(fs, "trace-host-sync")
    assert len(suppressed(fs, "trace-host-sync")) == 1


# ---------------------------------------------------------------------------
# CFG builder (tools/analysis/cfg.py)
# ---------------------------------------------------------------------------

def _build(src, name):
    import ast as _ast
    from tools.analysis.cfg import build_cfg
    tree = _ast.parse(textwrap.dedent(src))
    fn = next(n for n in _ast.walk(tree)
              if isinstance(n, (_ast.FunctionDef, _ast.AsyncFunctionDef))
              and n.name == name)
    return build_cfg(fn), fn, tree


def _lockset_at(src, name, lineno, must=True):
    """Lock-set fact at the entry of the node anchored at ``lineno``."""
    from tools.analysis.dataflow import LockModel, ModuleFunctions, \
        held_names, lock_facts
    cfg, fn, tree = _build(src, name)
    locks = LockModel(tree, "m")
    funcs = ModuleFunctions(tree)
    facts = lock_facts(cfg, locks, fn, funcs.class_of(fn), must=must)
    out = None
    for node in cfg.nodes():
        if node.lineno == lineno and id(node) in facts:
            fact = held_names(facts[id(node)])
            out = fact if out is None else (out & fact if must
                                            else out | fact)
    return out


_LOOP_LOCK_SRC = """
    import threading

    _lock = threading.Lock()

    def f(xs):
        total = 0
        for x in xs:
            with _lock:
                total += x           # line 10: lock held
        return total                 # line 11: released every iteration
"""


def test_cfg_loop_carried_lock_state():
    assert _lockset_at(_LOOP_LOCK_SRC, "f", 10) == frozenset({"m:_lock"})
    assert _lockset_at(_LOOP_LOCK_SRC, "f", 11) == frozenset()


_EARLY_RETURN_SRC = """
    import threading

    _lock = threading.Lock()

    def f(a):
        with _lock:
            if a:
                return 1             # line 9: exits through __exit__
        return 2                     # line 10: lock long gone
"""


def test_cfg_early_return_releases_with_block():
    assert _lockset_at(_EARLY_RETURN_SRC, "f", 9) == \
        frozenset({"m:_lock"})
    assert _lockset_at(_EARLY_RETURN_SRC, "f", 10) == frozenset()
    # and the early return actually reaches the function exit
    cfg, _, _ = _build(_EARLY_RETURN_SRC, "f")
    kinds = {n.kind for n in cfg.nodes()}
    assert "with_exit" in kinds and "exit" in kinds


def test_cfg_try_finally_resource_release(tmp_path):
    # finally-release survives the exceptional path: no leak finding;
    # dropping the finally turns it into one
    clean = lint(tmp_path, """
        def read(path, risky):
            f = open(path)
            try:
                return risky(f.name)
            finally:
                f.close()
        """)
    assert not fired(clean, "resource-leak-on-error")
    leaky = lint(tmp_path, """
        def read(path, risky):
            f = open(path)
            out = risky(f.name)      # raises -> f leaks
            f.close()
            return out
        """, name="leaky.py")
    assert len(fired(leaky, "resource-leak-on-error")) == 1


def test_cfg_async_def_is_skipped_not_guessed(tmp_path):
    # the builder declines async defs...
    cfg, _, _ = _build("async def f():\n    return 1", "f")
    assert cfg is None
    # ...and every CFG-hosted rule treats that as "not analyzed": no
    # crash, no false positive, even on a body that would fire if sync
    fs = lint(tmp_path, """
        import queue
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(2)
                self._t = threading.Thread(target=self._run)

            def _run(self):
                self.depth = 1

            async def weird(self):
                with self._lock:
                    return self._q.get()

            async def leaky(self, path):
                f = open(path)
                self._q.get()
                f.close()
        """)
    for rid in ("blocking-under-lock", "resource-leak-on-error",
                "thread-unlocked-attr"):
        assert not fired(fs, rid), rid


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------

_BLOCKING_BAD = """
    import queue
    import threading
    import time

    class Pump:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue(4)

        def bad_get(self):
            with self._lock:
                return self._q.get()         # BAD: unbounded get

        def bad_sleep(self):
            with self._lock:
                time.sleep(1.0)              # BAD: sleep under lock

        def _helper(self):
            return self._q.get()             # BAD when caller holds lock

        def bad_via_helper(self):
            with self._lock:
                return self._helper()
"""


def test_blocking_under_lock_bad(tmp_path):
    hits = fired(lint(tmp_path, _BLOCKING_BAD), "blocking-under-lock")
    assert len(hits) == 3, [f"{f.line}: {f.message}" for f in hits]
    joined = " ".join(f.message for f in hits)
    assert "Queue.get" in joined and "sleep" in joined
    assert "reached via" in joined          # the interprocedural one


def test_blocking_under_lock_clean(tmp_path):
    fs = lint(tmp_path, """
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(4)
                self._cache = {}

            def ok(self, k):
                with self._lock:
                    a = self._q.get_nowait()       # non-blocking: fine
                    b = self._q.get(timeout=0.1)   # bounded: fine
                    c = self._cache.get(k)         # dict.get: not a queue
                d = self._q.get()                  # lock released: fine
                return a, b, c, d

            def drain(self, timeout=None):
                with self._lock:
                    self._stopped = True
                self._thread.join(timeout)         # outside the lock
        """)
    assert not fired(fs, "blocking-under-lock")


def test_blocking_under_lock_suppression(tmp_path):
    src = _BLOCKING_BAD.replace(
        "time.sleep(1.0)              # BAD: sleep under lock",
        "time.sleep(1.0)  "
        "# mxlint: disable=blocking-under-lock -- fixture: single-"
        "threaded test harness, lock uncontended by construction")
    fs = lint(tmp_path, src)
    assert len(fired(fs, "blocking-under-lock")) == 2
    assert len(suppressed(fs, "blocking-under-lock")) == 1


def test_blocking_under_lock_fire_point(tmp_path):
    # a fault.fire() site is a raise point AND nests the registry lock
    fs = lint(tmp_path, """
        import threading
        from mxnet_tpu import fault

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            def admit(self, req):
                with self._lock:
                    fault.fire("serving.admit")    # BAD
                    return req

            def admit_ok(self, req):
                fault.fire("serving.admit")        # outside: fine
                with self._lock:
                    return req
        """)
    hits = fired(fs, "blocking-under-lock")
    assert len(hits) == 1 and "fault point" in hits[0].message


# ---------------------------------------------------------------------------
# lock-order-inversion
# ---------------------------------------------------------------------------

_LOCK_ORDER_BAD = """
    import threading

    class Duo:
        def __init__(self):
            self._mu = threading.Lock()
            self._nu = threading.Lock()

        def one(self):
            with self._mu:
                with self._nu:                 # mu -> nu
                    return 1

        def two(self):
            with self._nu:
                with self._mu:                 # nu -> mu: inversion
                    return 2
"""


def test_lock_order_inversion_bad(tmp_path):
    hits = fired(lint(tmp_path, _LOCK_ORDER_BAD), "lock-order-inversion")
    assert hits, "no inversion reported"
    joined = " ".join(f.message for f in hits)
    assert "Duo._mu" in joined and "Duo._nu" in joined


def test_lock_order_inversion_through_helper(tmp_path):
    # the second-order edge: a helper that takes nu is CALLED under mu
    # in one class, while another path takes them inverted
    fs = lint(tmp_path, """
        import threading

        class Duo:
            def __init__(self):
                self._mu = threading.Lock()
                self._nu = threading.Lock()

            def _inner(self):
                with self._nu:
                    return 1

            def outer(self):
                with self._mu:
                    return self._inner()       # mu -> nu via call

            def inverted(self):
                with self._nu:
                    with self._mu:             # nu -> mu
                        return 2
        """)
    assert fired(fs, "lock-order-inversion")


def test_lock_order_three_lock_cycle(tmp_path):
    # a -> c, c -> b, b -> a: no two-lock inversion anywhere, but the
    # three orders together deadlock — every edge of the cycle reports
    fs = lint(tmp_path, """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()
        _c = threading.Lock()

        def one():
            with _a:
                with _c:
                    return 1

        def two():
            with _c:
                with _b:
                    return 2

        def three():
            with _b:
                with _a:
                    return 3
        """)
    hits = fired(fs, "lock-order-inversion")
    assert len(hits) == 3, [f.message for f in hits]
    joined = " ".join(f.message for f in hits)
    assert "snippet.py:_a" in joined and "snippet.py:_b" in joined \
        and "snippet.py:_c" in joined


def test_blocking_under_lock_positional_timeout_is_bounded(tmp_path):
    # get(block, timeout) / put(item, block, timeout) positional forms
    # are bounded and must not fire
    fs = lint(tmp_path, """
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(4)

            def ok(self, item):
                with self._lock:
                    a = self._q.get(True, 0.1)
                    self._q.put(item, True, 0.1)
                return a
        """)
    assert not fired(fs, "blocking-under-lock")


def test_lock_order_same_name_different_files_not_conflated(tmp_path):
    # two FILES each defining a class named Worker with identically
    # named locks, in opposite orders: different lock objects, no
    # deadlock — tokens are file-qualified so no cycle appears
    one = """
        import threading

        class Worker:
            def __init__(self):
                self._mu = threading.Lock()
                self._nu = threading.Lock()

            def go(self):
                with self._mu:
                    with self._nu:
                        return 1
    """
    two = one.replace("with self._mu:", "with self._XX:").replace(
        "with self._nu:", "with self._mu:").replace(
        "with self._XX:", "with self._nu:")
    (tmp_path / "a.py").write_text(textwrap.dedent(one))
    (tmp_path / "b.py").write_text(textwrap.dedent(two))
    fs = analyze([tmp_path / "a.py", tmp_path / "b.py"], root=tmp_path)
    assert not fired(fs, "lock-order-inversion"), \
        [f.message for f in fired(fs, "lock-order-inversion")]


def test_lock_order_clean(tmp_path):
    fs = lint(tmp_path, """
        import threading

        class Duo:
            def __init__(self):
                self._mu = threading.Lock()
                self._nu = threading.Lock()

            def one(self):
                with self._mu:
                    with self._nu:
                        return 1

            def two(self):
                with self._mu:
                    with self._nu:
                        return 2               # same global order: fine
        """)
    assert not fired(fs, "lock-order-inversion")


def test_lock_order_suppression(tmp_path):
    src = _LOCK_ORDER_BAD.replace(
        "with self._mu:                 # nu -> mu: inversion",
        "with self._mu:  "
        "# mxlint: disable=lock-order-inversion -- fixture: two() only "
        "ever runs single-threaded during shutdown")
    fs = lint(tmp_path, src)
    assert len(suppressed(fs, "lock-order-inversion")) >= 1
    # the OTHER direction's site may still be reported (it is half of
    # the same cycle) — what matters is the waived edge is waived
    assert all(f.line != 16 for f in fired(fs, "lock-order-inversion"))


# ---------------------------------------------------------------------------
# signal-handler-unsafe
# ---------------------------------------------------------------------------

_SIGNAL_BAD = """
    import signal
    import threading

    _lock = threading.Lock()

    def handler(signum, frame):
        with _lock:                    # BAD: lock in handler
            pass
        print("dying")                 # BAD: I/O in handler
        raise RuntimeError("boom")     # BAD: non-exit raise

    signal.signal(signal.SIGTERM, handler)
"""


def test_signal_handler_unsafe_bad(tmp_path):
    hits = fired(lint(tmp_path, _SIGNAL_BAD), "signal-handler-unsafe")
    assert len(hits) == 3, [f.message for f in hits]
    joined = " ".join(f.message for f in hits)
    assert "acquires" in joined and "print" in joined \
        and "RuntimeError" in joined


def test_signal_handler_clean_latch(tmp_path):
    # the GracefulExit pattern: set flags, remember the signum, at most
    # re-raise KeyboardInterrupt — nothing to report
    fs = lint(tmp_path, """
        import signal

        class Latch:
            def __init__(self):
                self.requested = False
                self.signum = None
                self._prev = {}

            def _on_signal(self, signum, frame):
                if self.requested:
                    raise KeyboardInterrupt    # conventional: fine
                self.requested = True
                self.signum = signum

            def __enter__(self):
                for s in (signal.SIGTERM, signal.SIGINT):
                    self._prev[s] = signal.signal(s, self._on_signal)
                return self
        """)
    assert not fired(fs, "signal-handler-unsafe")


def test_signal_handler_unsafe_helper_and_suppression(tmp_path):
    fs = lint(tmp_path, """
        import signal
        import threading

        _lock = threading.Lock()

        def _record():
            with _lock:                # BAD: called from the handler
                pass

        def handler(signum, frame):
            _record()

        signal.signal(signal.SIGTERM, handler)
        """)
    hits = fired(fs, "signal-handler-unsafe")
    assert len(hits) == 1 and "via" in hits[0].message
    src = _SIGNAL_BAD.replace(
        'print("dying")                 # BAD: I/O in handler',
        'print("dying")  '
        '# mxlint: disable=signal-handler-unsafe -- fixture: diagnostic '
        'of last resort on the exit path, torn output acceptable')
    fs2 = lint(tmp_path, src, name="sig2.py")
    assert len(fired(fs2, "signal-handler-unsafe")) == 2
    assert len(suppressed(fs2, "signal-handler-unsafe")) == 1


# ---------------------------------------------------------------------------
# resource-leak-on-error
# ---------------------------------------------------------------------------

_LEAK_BAD = """
    import threading

    def leak_file(path, risky):
        f = open(path)
        data = risky(f.name)         # raises -> f leaks
        f.close()
        return data

    def leak_thread(work):
        t = threading.Thread(target=work)
        t.start()
        work()                       # raises -> t never joined
        t.join()
"""


def test_resource_leak_bad(tmp_path):
    hits = fired(lint(tmp_path, _LEAK_BAD), "resource-leak-on-error")
    assert len(hits) == 2, [f"{f.line}: {f.message}" for f in hits]
    joined = " ".join(f.message for f in hits)
    assert "file handle" in joined and "started thread" in joined


def test_resource_leak_clean(tmp_path):
    fs = lint(tmp_path, """
        import threading

        def ok_with(path, risky):
            with open(path) as f:
                return risky(f.name)

        def ok_finally(path, risky):
            f = open(path)
            try:
                return risky(f.name)
            finally:
                f.close()

        def ok_escape_self(self, work):
            t = threading.Thread(target=work)
            t.start()
            self._threads.append(t)    # ownership handed off
            work()

        def ok_unstarted(work, risky):
            t = threading.Thread(target=work)
            risky()                    # t never started: no obligation
            t.start()
            t.join()

        def ok_return(path):
            f = open(path)
            return f                   # constructor pattern: caller owns
        """)
    assert not fired(fs, "resource-leak-on-error")


def test_resource_leak_suppression(tmp_path):
    src = _LEAK_BAD.replace(
        "f = open(path)",
        "f = open(path)  "
        "# mxlint: disable=resource-leak-on-error -- fixture: process "
        "exits right after, the OS reaps the handle")
    fs = lint(tmp_path, src)
    assert len(fired(fs, "resource-leak-on-error")) == 1   # thread one
    assert len(suppressed(fs, "resource-leak-on-error")) == 1


def test_resource_leak_rebind_keeps_old_handle_on_raise(tmp_path):
    # `f = open(y)` over an earlier `f = open(x)`: if the second open
    # raises, the store never ran — the FIRST handle is still bound and
    # leaks (the acquiring statement's raise edge carries the
    # pre-statement state, not "nothing acquired")
    fs = lint(tmp_path, """
        def f(a, b):
            h = open(a)
            h = open(b)
            h.close()
        """)
    hits = fired(fs, "resource-leak-on-error")
    assert len(hits) == 1 and hits[0].line == 3, \
        [f"{x.line}: {x.message}" for x in hits]


def test_blocking_under_lock_false_value_still_blocks(tmp_path):
    # q.put(False) enqueues the VALUE False — it blocks like any put;
    # only the block-FLAG slot (or block=False) means non-blocking
    fs = lint(tmp_path, """
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(4)

            def bad(self):
                with self._lock:
                    self._q.put(False)           # BAD: blocking put

            def ok(self):
                with self._lock:
                    self._q.put(1, False)        # block-flag: fine
                    self._q.get(block=False)     # keyword flag: fine
        """)
    hits = fired(fs, "blocking-under-lock")
    assert len(hits) == 1 and hits[0].line == 12, \
        [f"{x.line}: {x.message}" for x in hits]


def test_reentrant_lock_nesting_balances(tmp_path):
    # `with self._lock:` inside `with self._lock:` (RLock): the inner
    # exit must not release the outer hold — the access after the
    # inner block is still locked (thread rule), and a blocking op
    # there is still under-lock (blocking rule)
    fs = lint(tmp_path, """
        import queue
        import threading

        class Feed:
            def __init__(self):
                self._lock = threading.RLock()
                self._q = queue.Queue(2)
                self.count = 0
                self._t = threading.Thread(target=self._produce)

            def _produce(self):
                with self._lock:
                    self.count += 1

            def read(self):
                with self._lock:
                    with self._lock:
                        a = self.count
                    b = self.count       # outer lock STILL held: fine
                return a + b

            def bad(self):
                with self._lock:
                    with self._lock:
                        pass
                    self._q.get()        # BAD: outer lock still held
        """)
    assert not fired(fs, "thread-unlocked-attr"), \
        [f.message for f in fired(fs, "thread-unlocked-attr")]
    hits = fired(fs, "blocking-under-lock")
    assert len(hits) == 1 and "Queue.get" in hits[0].message


def test_blocking_under_lock_thread_list_join(tmp_path):
    # the PrefetchingIter shape: threads kept in a self._threads list,
    # joined in a loop — under a lock that loop join must be flagged
    fs = lint(tmp_path, """
        import threading

        class Feed:
            def __init__(self):
                self._lock = threading.Lock()
                self._threads = []
                for i in range(2):
                    self._threads.append(
                        threading.Thread(target=self._run))

            def _run(self):
                pass

            def stop_bad(self):
                with self._lock:
                    for t in self._threads:
                        t.join()             # BAD: join under lock

            def stop_ok(self):
                with self._lock:
                    threads = list(self._threads)
                for t in self._threads:
                    t.join()                 # outside the lock: fine
                return threads
        """)
    hits = fired(fs, "blocking-under-lock")
    assert len(hits) == 1 and "join" in hits[0].message, \
        [f"{f.line}: {f.message}" for f in hits]


def test_blocking_under_lock_only_local_locks(tmp_path):
    # a module whose ONLY lock is function-local must still be swept
    fs = lint(tmp_path, """
        import queue
        import threading

        _q = queue.Queue(2)

        def g():
            local = threading.Lock()
            with local:
                return _q.get()          # BAD: blocking under lock
        """)
    assert len(fired(fs, "blocking-under-lock")) == 1


def test_trace_membership_numeric_vs_key(tmp_path):
    # `0 in x` on a traced array is an element comparison (flags);
    # `"k" in store` / `name in store` are key probes (exempt)
    fs = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x, store):
            if 0 in x:                   # BAD: concretizes the tracer
                return 1
            if "k" in store:             # key probe: fine
                return 2
            if x.ndim in store:          # static metadata key: fine
                return 3
            return 4
        """)
    hits = fired(fs, "trace-python-branch")
    assert len(hits) == 1, [f.message for f in hits]


def test_blocking_under_lock_local_lock_acquire(tmp_path):
    # a function-LOCAL lock blocking-acquired under a held lock
    fs = lint(tmp_path, """
        import threading

        _g = threading.Lock()

        def f():
            local = threading.Lock()
            with _g:
                local.acquire()                  # BAD: nested blocking
            local.release()
        """)
    hits = fired(fs, "blocking-under-lock")
    assert len(hits) == 1 and "acquire" in hits[0].message


def test_resource_leak_prefetcher(tmp_path):
    # the exact bug shape PR 1/2 fixed by hand: a wrapped feed whose
    # close() is unreachable when the loop body raises
    fs = lint(tmp_path, """
        def train(base, step):
            it = PrefetchingIter(base)
            for batch in it:
                step(batch)            # raises -> producer threads leak
            it.close()

        def train_ok(base, step):
            it = PrefetchingIter(base)
            try:
                for batch in it:
                    step(batch)
            finally:
                it.close()
        """)
    hits = fired(fs, "resource-leak-on-error")
    assert len(hits) == 1 and "prefetcher" in hits[0].message


def test_donated_reuse_same_statement(tmp_path):
    # the donation and the stale read share one statement: evaluation
    # order (call ends before the later read) still flags it — the
    # PR 3 textual model, preserved within a CFG node
    fs = lint(tmp_path, """
        import jax

        def run(batch):
            step = jax.jit(lambda a: a + 1, donate_argnums=(0,))
            out = (step(batch), batch.sum())   # BAD: read after donate
            return out
        """)
    assert len(fired(fs, "donated-batch-reuse")) == 1


def test_blocking_under_lock_lambda_is_deferred(tmp_path):
    # a lambda body runs at its call site, not where the literal sits:
    # constructing a worker under the lock must not count as blocking
    fs = lint(tmp_path, """
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(4)

            def spawn(self):
                with self._lock:
                    t = threading.Thread(target=lambda: self._q.get())
                t.start()
                return t
        """)
    assert not fired(fs, "blocking-under-lock")


def test_lock_order_inversion_multi_item_with(tmp_path):
    # `with a, b:` acquires left to right — inverting it with nested
    # withs elsewhere is the same ABBA deadlock
    fs = lint(tmp_path, """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def one():
            with _a, _b:
                return 1

        def two():
            with _b:
                with _a:
                    return 2
        """)
    assert fired(fs, "lock-order-inversion")


# ---------------------------------------------------------------------------
# compile-boundary family (ISSUE 6: the costguard surface)
# ---------------------------------------------------------------------------

def test_jit_in_loop_bad(tmp_path):
    fs = lint(tmp_path, """
        import functools
        import jax

        def retrace_everything(fns, xs, step):
            outs = []
            for f in fns:
                g = jax.jit(f)              # fresh wrapper every pass
                outs.append(g(xs))
            while xs:
                h = functools.partial(jax.jit, static_argnums=(1,))(step)
                xs = h(xs, 1)
            wrappers = [jax.jit(f) for f in fns]
            for x in xs:
                step.lower(x).compile()     # AOT compile per iteration
            return outs, wrappers
        """)
    assert len(fired(fs, "jit-in-loop")) == 4, \
        [f.message for f in fired(fs, "jit-in-loop")]


def test_jit_in_loop_per_request_path(tmp_path):
    # the serving failure mode: a handler that builds the jit per call —
    # the executable cache hangs off the wrapper, so every request pays
    # a full XLA compile
    fs = lint(tmp_path, """
        import jax

        def handle(model, request):
            return jax.jit(model)(request)
        """)
    msgs = fired(fs, "jit-in-loop")
    assert len(msgs) == 1 and "EVERY call" in msgs[0].message


def test_jit_in_loop_clean(tmp_path):
    # module-scope construction (INCLUDING loops/comprehensions there —
    # import runs once, and a bounded wrapper registry is this rule's
    # own fix advice), cache-guarded per-signature slots (the executor
    # pattern), *calling* a jitted fn in a loop, and the
    # str.lower()/re.compile lookalikes must all stay silent
    fs = lint(tmp_path, """
        import re
        import jax

        jitted = jax.jit(lambda x: x * 2)
        KERNELS = {name: jax.jit(fn)            # bind-once registry:
                   for name, fn in [("a", abs)]}  # once per import
        for _extra in (min, max):
            KERNELS[_extra.__name__] = jax.jit(_extra)

        class Executor:
            def __init__(self):
                self._jit_cache = {}

            def run(self, key, fn, x):
                if key not in self._jit_cache:
                    self._jit_cache[key] = jax.jit(fn)
                return self._jit_cache[key](x)

        def warmup(server, samples):
            for s in samples:
                jitted(s)                   # executing, not constructing
            for fn in samples:
                if fn.lower().endswith(".jpg"):
                    continue
            else:
                g = jax.jit(len)            # else: runs ONCE, after the loop
            pats = [re.compile(p) for p in ("a", "b")]
            return pats, g
        """)
    assert not fired(fs, "jit-in-loop"), \
        [f.message for f in fired(fs, "jit-in-loop")]


def test_jit_in_loop_suppression(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def census(apply, avals):
            outs = []
            for a in avals:
                # mxlint: disable=jit-in-loop -- bounded bucket-grid
                # enumeration; compiles are memoized downstream
                outs.append(apply.lower(a).compile())
            return outs
        """)
    assert not fired(fs, "jit-in-loop")
    assert len(suppressed(fs, "jit-in-loop")) == 1


def test_unbudgeted_entrypoint_bad(tmp_path):
    fs = lint(tmp_path, """
        from tools.costguard import entrypoint

        @entrypoint("my_new_model_train")
        def build_my_new_model_train():
            pass
        """)
    msgs = fired(fs, "unbudgeted-entrypoint")
    # a registration owes BOTH gate goldens: the costguard budget AND
    # the hloguard structural census (ISSUE 18) — one finding per
    # registration, naming every missing golden
    assert len(msgs) == 1
    assert "goldens/budgets/my_new_model_train.json" in msgs[0].message
    assert "goldens/hloguard/my_new_model_train.json" in msgs[0].message
    assert "regen_hloguard.py" in msgs[0].message


def test_unbudgeted_entrypoint_hloguard_golden_alone_missing(tmp_path):
    gdir = tmp_path / "tests" / "goldens" / "budgets"
    gdir.mkdir(parents=True)
    (gdir / "my_new_model_train.json").write_text("{}")
    fs = lint(tmp_path, """
        from tools.costguard import entrypoint

        @entrypoint("my_new_model_train")
        def build_my_new_model_train():
            pass
        """)
    msgs = fired(fs, "unbudgeted-entrypoint")
    assert len(msgs) == 1
    assert "goldens/hloguard/my_new_model_train.json" in msgs[0].message
    assert "goldens/budgets" not in msgs[0].message


def test_unbudgeted_entrypoint_clean_with_golden(tmp_path):
    for sub in ("budgets", "hloguard"):
        gdir = tmp_path / "tests" / "goldens" / sub
        gdir.mkdir(parents=True)
        (gdir / "my_new_model_train.json").write_text("{}")
    fs = lint(tmp_path, """
        from tools.costguard import entrypoint

        @entrypoint("my_new_model_train")
        def build_my_new_model_train():
            pass
        """)
    assert not fired(fs, "unbudgeted-entrypoint")


def test_unbudgeted_entrypoint_suppression(tmp_path):
    fs = lint(tmp_path, """
        from tools.costguard import entrypoint

        # mxlint: disable=unbudgeted-entrypoint -- golden lands in the
        # follow-up PR that wires this model's serving path
        @entrypoint("my_new_model_train")
        def build_my_new_model_train():
            pass
        """)
    assert not fired(fs, "unbudgeted-entrypoint")
    assert len(suppressed(fs, "unbudgeted-entrypoint")) == 1


# ---------------------------------------------------------------------------
# registry + docs consistency
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# spmd family (ISSUE 11): axis binding, spec arity, replication claims,
# collectives in Python loops
# ---------------------------------------------------------------------------

def test_spmd_axis_unknown_bad(tmp_path):
    # a literal axis the (literal) mesh does not define — the typo that
    # otherwise compiles and fails deep inside jax
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        def body(x):
            i = jax.lax.axis_index("tp")      # BAD: mesh is dp-only
            return jax.lax.psum(x, "pd")      # BAD: typo'd dp

        def run(x):
            mesh = make_mesh(dp=8)
            return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P("dp"))(x)
        """)
    assert len(fired(fs, "spmd-axis-unknown")) == 2, \
        [f.message for f in fs]


def test_spmd_axis_unknown_outside_shard_map(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def reduce_all(x):
            return jax.lax.psum(x, "dp")   # BAD: no binder anywhere
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "no enclosing shard_map" in msgs[0].message


def test_spmd_axis_unknown_interprocedural(tmp_path):
    # the literal axis crosses a helper call boundary (the same
    # two-level inlining as trace taint) and carries a via-chain
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        def reduce_over(x, axis):
            return jax.lax.psum(x, axis)

        def body(x):
            return reduce_over(x, "tp")    # BAD: dp mesh

        def run(x):
            mesh = make_mesh(dp=8)
            return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P("dp"))(x)
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "via body" in msgs[0].message


def test_spmd_axis_unknown_clean_open_binding(tmp_path):
    # a mesh/specs arriving through variables is an OPEN binding: the
    # rule must never guess — and axes passed as parameters are not
    # literals, so library helpers stay silent
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x):
            return jax.lax.psum(x, "dp")

        def run(mesh, specs, x):
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)(x)

        def ring(x, axis, n):
            perm = [(j, (j + 1) % n) for j in range(n)]
            return jax.lax.ppermute(x, axis, perm)
        """)
    assert not fired(fs, "spmd-axis-unknown")


def test_spmd_axis_unknown_spec_vs_literal_mesh(tmp_path):
    # a spec naming an axis outside a LITERAL mesh is the same typo
    # class, anchored at the spec
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        def body(x):
            return x

        def run(x):
            mesh = make_mesh(dp=8)
            return shard_map(body, mesh=mesh, in_specs=(P("db"),),
                             out_specs=P("dp"))(x)
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "'db'" in msgs[0].message


def test_spmd_axis_unknown_default_and_dict_mesh_forms(tmp_path):
    # regression: make_mesh() (documented default: one 'dp' axis) and
    # the axes= dict-literal form resolve CLOSED with the right axes —
    # valid code must not be flagged, typos still are
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        def body(x):
            return jax.lax.psum(x, "dp")       # fine: default dp mesh

        def body2(x):
            return jax.lax.psum(x, "tp")       # fine: axes dict has tp

        def body3(x):
            return jax.lax.psum(x, "pd")       # BAD: typo under dict

        def run(x):
            mesh = make_mesh()
            return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P("dp"))(x)

        def run2(x):
            mesh = make_mesh(axes={"dp": 2, "tp": 4})
            a = shard_map(body2, mesh=mesh, in_specs=(P("tp"),),
                          out_specs=P("tp"))(x)
            b = shard_map(body3, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"))(x)
            return a, b
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "'pd'" in msgs[0].message, \
        [f.message for f in fs]


def test_spmd_axis_unknown_param_shadows_module_mesh(tmp_path):
    # regression: a PARAMETER named like a module-level mesh must not
    # resolve to the module literal — the runtime mesh is unknown, the
    # binding stays open, valid axes stay silent
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(dp=8)

        def body(x):
            return jax.lax.psum(x, "tp")

        def run(x, mesh):
            return shard_map(body, mesh=mesh, in_specs=(P("tp"),),
                             out_specs=P("tp"))(x)
        """)
    assert not fired(fs, "spmd-axis-unknown"), \
        [f.message for f in fs]


def test_spmd_axis_unknown_tuple_unpack_shadows_module_mesh(tmp_path):
    # regression: tuple-unpacking rebinds (`mesh, opt = _mesh_and_opt()`
    # — the repo's own idiom) must kill a same-named module literal:
    # the runtime mesh is unknown, the binding stays open
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(dp=8)

        def body(x):
            return jax.lax.psum(x, "tp")

        def run(x):
            mesh, opt = build_mesh_and_opt()
            return shard_map(body, mesh=mesh, in_specs=(P("tp"),),
                             out_specs=P("tp"))(x)
        """)
    assert not fired(fs, "spmd-axis-unknown"), \
        [f.message for f in fs]


def test_spmd_axis_unknown_nested_regions(tmp_path):
    # regression: a shard_map body NESTED inside another shard_map body
    # (the TP-inside-dp shape ROADMAP item 1 builds) carries its own
    # axis binding — judged by its own region, not the outer one's;
    # a genuine typo in the inner region still fires
    fs = lint(tmp_path, """
        import functools
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh
        from jax.sharding import PartitionSpec as P

        def run(x, tp_mesh):
            dp_mesh = make_mesh(dp=8)

            def outer_body(xl):
                @functools.partial(shard_map, mesh=tp_mesh,
                                   in_specs=(P("tp"),),
                                   out_specs=P("tp"))
                def inner(y):
                    return jax.lax.psum(y, "tp")   # fine: inner binds tp

                return inner(jax.lax.psum(xl, "dp"))

            return shard_map(outer_body, mesh=dp_mesh,
                             in_specs=(P("dp"),), out_specs=P("dp"))(x)

        def run2(x):
            dp_mesh = make_mesh(dp=8)

            def outer_body(xl):
                @functools.partial(shard_map, mesh=make_mesh(tp=8),
                                   in_specs=(P("tp"),),
                                   out_specs=P("tp"))
                def inner(y):
                    return jax.lax.psum(y, "pt")   # BAD: inner typo

                return inner(xl)

            return shard_map(outer_body, mesh=dp_mesh,
                             in_specs=(P("dp"),), out_specs=P("dp"))(x)
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "'pt'" in msgs[0].message, \
        [f.message for f in fs]


def test_spmd_axis_unknown_mixed_axis_open_mesh(tmp_path):
    # regression: with a NON-literal mesh, a body collective over an
    # axis absent from the (fully literal) specs is valid mixed-axis
    # code — the runtime mesh may define it; specs alone must never
    # close the binding
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x):
            return jax.lax.psum(x, "tp")

        def run(self_mesh, x):
            return shard_map(body, mesh=self_mesh,
                             in_specs=(P("dp"),),
                             out_specs=(P("dp"),))(x)
        """)
    assert not fired(fs, "spmd-axis-unknown"), \
        [f.message for f in fs]


def test_spmd_scope_assignments_shadowing(tmp_path):
    # regression: every shadowing binder — nested def/class, imports,
    # tuple unpacking — kills a same-named module-level literal in the
    # resolution map (a stale literal would wrongly CLOSE an axis set)
    import ast as _ast

    from tools.analysis.dataflow import scope_assignments
    src = textwrap.dedent("""
        from mxnet_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(dp=8)
        grid = make_mesh(tp=8)
        spec = make_mesh(ep=8)

        def run(x):
            def mesh():
                pass
            grid, opt = build()
            import numpy as spec
            return x
        """)
    tree = _ast.parse(src)
    fn = next(n for n in _ast.walk(tree)
              if isinstance(n, _ast.FunctionDef) and n.name == "run")
    assigns = scope_assignments(fn, tree)
    assert "mesh" not in assigns
    assert "grid" not in assigns
    assert "spec" not in assigns


def test_spmd_axis_unknown_suppression(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def reduce_all(x):
            return jax.lax.psum(x, "dp")  # mxlint: disable=spmd-axis-unknown -- fixture: caller wraps in shard_map cross-module
        """)
    assert not fired(fs, "spmd-axis-unknown")
    assert suppressed(fs, "spmd-axis-unknown")


def test_spmd_spec_arity_bad(tmp_path):
    fs = lint(tmp_path, """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x, y):
            return x + y, x - y

        def run(mesh, x, y):
            return shard_map(body, mesh=mesh,
                             in_specs=(P("dp"), P("dp"), P()),
                             out_specs=(P("dp"),))(x, y)
        """)
    msgs = fired(fs, "spmd-spec-arity")
    assert len(msgs) == 2, [f.message for f in fs]
    assert any("3 entries" in m.message and "at most 2" in m.message
               for m in msgs)
    assert any("returns 2" in m.message for m in msgs)


def test_spmd_spec_arity_rank(tmp_path):
    # PartitionSpec longer than the statically-known argument rank
    fs = lint(tmp_path, """
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(z):
            return z

        def run(mesh):
            z = jnp.zeros((8,))
            return shard_map(body, mesh=mesh,
                             in_specs=(P("dp", None),),
                             out_specs=P("dp"))(z)
        """)
    msgs = fired(fs, "spmd-spec-arity")
    assert len(msgs) == 1 and "rank 1" in msgs[0].message


def test_spmd_spec_arity_clean(tmp_path):
    # matching arity, *leaves varargs (the step.py shape), and defaults
    fs = lint(tmp_path, """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x, y):
            return x + y, x - y

        def var_body(a, *leaves):
            return a

        def run(mesh, x, y, batch):
            good = shard_map(body, mesh=mesh,
                             in_specs=(P("dp"), P("dp")),
                             out_specs=(P("dp"), P("dp")))(x, y)
            ok = shard_map(var_body, mesh=mesh,
                           in_specs=(P(),) + tuple([P("dp")] * 4),
                           out_specs=P())(x, *batch)
            return good, ok
        """)
    assert not fired(fs, "spmd-spec-arity")


def test_spmd_spec_arity_rank_starred_args_bail(tmp_path):
    # regression: a *star argument expands to an unknown count, so AST
    # indices after it no longer align with specs — the rank check must
    # stop, not flag correct code
    fs = lint(tmp_path, """
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(a, b, z):
            return z

        def run(mesh, pair):
            z = jnp.zeros((8,))
            return shard_map(body, mesh=mesh,
                             in_specs=(P("dp"), P("dp", None), P("dp")),
                             out_specs=P("dp"))(*pair, z)
        """)
    assert not fired(fs, "spmd-spec-arity"), \
        [f.message for f in fired(fs, "spmd-spec-arity")]


def test_spmd_axis_unknown_lambda_bodies(tmp_path):
    # regression: a collective hidden in a lambda is still swept when
    # no binder exists — and a shard_map-wrapped lambda is covered
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def outer(xs):
            f = lambda x: jax.lax.psum(x, "dp")   # BAD: no binder
            return [f(x) for x in xs]

        def run(mesh, x):
            return shard_map(lambda a: jax.lax.psum(a, "dp"),
                             mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P())(x)    # covered: no sweep
        """)
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "<lambda>" in msgs[0].message, \
        [f.message for f in fs]


def test_spmd_stored_curried_wrap_literal_mesh(tmp_path):
    # the ISSUE 14 builder idiom: the mesh rides a STORED curried
    # wrapper (wrap = partial(shard_map, mesh=...)), the body and the
    # specs arrive at the application site — the body is judged
    # against the partial's mesh axes, not swept as unbound
    good = """
        import functools
        import jax
        from mxnet_tpu.parallel.mesh import make_mesh, shard_map
        from jax.sharding import PartitionSpec as P

        def build(nh):
            mesh = make_mesh(tp=8)
            wrap = functools.partial(shard_map, mesh=mesh,
                                     check_vma=False)

            def body(x):
                return jax.lax.psum(x, "tp")

            return wrap(body, in_specs=(P("tp"),), out_specs=P())
        """
    fs = lint(tmp_path, good)
    assert not fired(fs, "spmd-axis-unknown"), \
        [f.message for f in fired(fs, "spmd-axis-unknown")]
    fs = lint(tmp_path, good.replace('"tp")\n', '"pt")  # BAD: typo\n', 1))
    msgs = fired(fs, "spmd-axis-unknown")
    assert len(msgs) == 1 and "'pt'" in msgs[0].message, \
        [f.message for f in fs]


def test_spmd_stored_curried_wrap_open_mesh_skipped(tmp_path):
    # a curried wrapper whose mesh is a runtime value (the
    # cross-function generate.py builder shape) stays an OPEN
    # binding: collectives inside are not guessed at, and
    # parallel.mesh.validate_specs owns the axis-typo class at call
    # time
    fs = lint(tmp_path, """
        import functools
        import jax
        from mxnet_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        def build(mesh, axis):
            wrap = functools.partial(shard_map, mesh=mesh,
                                     check_vma=False)

            def body(x):
                return jax.lax.psum(x, "tp")

            return wrap(body, in_specs=(P("tp"),), out_specs=P())
        """)
    assert not fired(fs, "spmd-axis-unknown"), \
        [f.message for f in fired(fs, "spmd-axis-unknown")]


def test_spmd_gate_discovers_tp_decode_regions():
    """Non-vacuous proof the family sees the ISSUE 14 tensor-parallel
    decode surface: the serving builders' stored-curried ``shard_map``
    regions in ``serving/generate.py`` are discovered (as OPEN-mesh
    anchors — the mesh is a server ctor argument, so the binding is
    runtime-validated by ``parallel.mesh.validate_specs``, not
    guessed), and the whole TP surface carries zero unsuppressed
    spmd findings."""
    import ast

    from tools.analysis.spmd_rules import find_regions

    src = (REPO / "mxnet_tpu" / "serving" / "generate.py").read_text()
    regions = find_regions(ast.parse(src))
    assert regions, "no shard_map regions discovered in generate.py"
    assert all(not r.closed for r in regions), \
        "generate.py builder meshes are ctor args — expected OPEN"
    tp_surface = [REPO / "mxnet_tpu" / "serving" / "generate.py",
                  REPO / "mxnet_tpu" / "gluon" / "model_zoo"
                       / "causal_lm.py",
                  REPO / "mxnet_tpu" / "parallel" / "quantize.py",
                  REPO / "mxnet_tpu" / "parallel" / "sharding.py"]
    findings = analyze(tp_surface, root=REPO, use_cache=True)
    live = [f for f in findings
            if f.rule.startswith("spmd-") and not f.suppressed]
    assert not live, "\n".join(f.render() for f in live)


def test_spmd_spec_arity_suppression(tmp_path):
    fs = lint(tmp_path, """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x):
            return x

        def run(mesh, x, y):
            # mxlint: disable=spmd-spec-arity -- fixture: wrapper feeds body via *args trampoline
            return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P())(x, y)
        """)
    assert not fired(fs, "spmd-spec-arity")
    assert suppressed(fs, "spmd-spec-arity")


_SPMD_INT8_PATH = """
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    def quantize(x):
        s = jnp.max(jnp.abs(x)) / 127.0
        return (x / s).astype(jnp.int8), s

    def dequantize(q, s):
        return q.astype(jnp.float32) * s

    def reduce_leaf(g, n_dev):
        q, s = quantize(g)
        q = lax.all_to_all(q, "dp", 0, 0, tiled=True)
        s = lax.all_to_all(s, "dp", 0, 0, tiled=True)
        owned = jnp.sum(dequantize(q, s), axis=0)
        q2, s2 = quantize(owned)
        gq = lax.all_gather(q2, "dp", axis=0)
        gs = lax.all_gather(s2, "dp", axis=0)
        return dequantize(gq, gs)

    def run(mesh, grads):
        return shard_map(reduce_leaf, mesh=mesh,
                         in_specs=(P("dp"), P()),
                         out_specs=P())(grads, 8)
"""

_SPMD_INT8_MUTATED = """
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    def quantize(x):
        s = jnp.max(jnp.abs(x)) / 127.0
        return (x / s).astype(jnp.int8), s

    def dequantize(q, s):
        return q.astype(jnp.float32) * s

    def reduce_leaf(g, n_dev):
        q, s = quantize(g)
        owned = jnp.sum(dequantize(q, s), axis=0)
        return owned / n_dev

    def run(mesh, grads):
        return shard_map(reduce_leaf, mesh=mesh,
                         in_specs=(P("dp"), P()),
                         out_specs=P())(grads, 8)
"""


def test_spmd_replication_claim_int8_path(tmp_path):
    """The ISSUE's acceptance pair: the two-phase int8 exchange of
    ``reduce_gradients`` (every device dequantizes identical all_gather
    payloads) honestly claims replication — CLEAN; strip the gathers
    (return the per-device partial) and the same claim is unsound —
    FLAGGED.  The statically checkable core of check_vma."""
    assert not fired(lint(tmp_path, _SPMD_INT8_PATH),
                     "spmd-replication-claim")
    msgs = fired(lint(tmp_path, _SPMD_INT8_MUTATED, name="mutated.py"),
                 "spmd-replication-claim")
    assert len(msgs) == 1 and "no psum/pmean/all_gather" in msgs[0].message


def test_spmd_replication_claim_partial_decorator(tmp_path):
    # the pipeline.py idiom: @functools.partial(shard_map, ...) with a
    # psum-produced output honestly replicated; the sibling claims
    # replication on a raw per-device value
    fs = lint(tmp_path, """
        import functools
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            @functools.partial(
                shard_map, mesh=mesh, in_specs=(P("dp"),),
                out_specs=P(), check_vma=False)
            def good(xl):
                return jax.lax.psum(xl, "dp")

            @functools.partial(
                shard_map, mesh=mesh, in_specs=(P("dp"),),
                out_specs=P(), check_vma=False)
            def bad(xl):
                return xl * 2

            return good(x), bad(x)
        """)
    msgs = fired(fs, "spmd-replication-claim")
    assert len(msgs) == 1 and "'bad'" in msgs[0].message


def test_spmd_replication_claim_all_replicated_inputs(tmp_path):
    # regression: in_specs=PartitionSpec() (jax's pytree-prefix
    # "everything replicated" form) makes the replicated out_specs
    # claim sound with NO reducer — identical inputs, identical math
    fs = lint(tmp_path, """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(x):
            return x * 2

        def run(mesh, x):
            return shard_map(body, mesh=mesh, in_specs=P(),
                             out_specs=P())(x)
        """)
    assert not fired(fs, "spmd-replication-claim"), \
        [f.message for f in fired(fs, "spmd-replication-claim")]


def test_spmd_replication_claim_conditional_reducer(tmp_path):
    # regression: the step.py loss-reduction idiom — a reducer picked
    # by a conditional expression — is still a reducer; a MIXED
    # dispatch (one branch does not reduce) stays unknown, not unsound
    fs = lint(tmp_path, """
        import jax
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        def body(x, mean):
            return (lax.pmean if mean else lax.psum)(x, "dp")

        def body2(x, mean):
            return (lax.pmean if mean else jax.numpy.sum)(x)

        def run(mesh, x, m):
            a = shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                          out_specs=P())(x, m)
            b = shard_map(body2, mesh=mesh, in_specs=(P("dp"), P()),
                          out_specs=P())(x, m)
            return a, b
        """)
    assert not fired(fs, "spmd-replication-claim"), \
        [f.message for f in fired(fs, "spmd-replication-claim")]


def test_spmd_replication_claim_suppression(tmp_path):
    fs = lint(tmp_path, """
        import functools
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(mesh, x):
            @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                               out_specs=P(), check_vma=False)
            def f(xl):
                # mxlint: disable=spmd-replication-claim -- fixture: inputs are verified replica-identical upstream
                return xl * 2
            return f(x)
        """)
    assert not fired(fs, "spmd-replication-claim")
    assert suppressed(fs, "spmd-replication-claim")


def test_spmd_collective_in_loop_bad(tmp_path):
    fs = lint(tmp_path, """
        import jax
        from jax import lax

        def reduce_layers(grads, axis):
            out = []
            for g in grads:                       # BAD: per-leaf psum
                out.append(lax.psum(g, axis))
            gathered = [lax.all_gather(g, axis) for g in grads]  # BAD
            return out, gathered
        """)
    assert len(fired(fs, "spmd-collective-in-loop")) == 2


def test_spmd_collective_in_loop_clean(tmp_path):
    # one fused collective outside the loop; loops that merely CALL a
    # collective-free fn; mx.distributed's one-argument host-level
    # all_gather lookalike
    fs = lint(tmp_path, """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from mxnet_tpu import distributed

        def fused(grads, axis):
            flat = jnp.concatenate([g.reshape(-1) for g in grads])
            total = lax.psum(flat, axis)
            return total

        def host_side(xs):
            return [distributed.all_gather(x) for x in xs]
        """)
    assert not fired(fs, "spmd-collective-in-loop")


def test_spmd_collective_in_loop_suppression(tmp_path):
    fs = lint(tmp_path, """
        import jax

        def ring(k, axis, n, perm):
            for step in range(n):
                # mxlint: disable=spmd-collective-in-loop -- fixture: deliberate ring schedule, one hop per step
                k = jax.lax.ppermute(k, axis, perm)
            return k
        """)
    assert not fired(fs, "spmd-collective-in-loop")
    assert suppressed(fs, "spmd-collective-in-loop")


def test_spmd_rules_multi_item_with_bound_shard_map(tmp_path):
    # the wrapper call sits inside a multi-item `with` (MeshScope +
    # something else): regions are still discovered and judged
    fs = lint(tmp_path, """
        import jax
        from jax import shard_map
        from mxnet_tpu.parallel.mesh import make_mesh, MeshScope
        from jax.sharding import PartitionSpec as P

        def body(x):
            return jax.lax.pmean(x, "pd")      # BAD: typo'd dp

        def run(x, lock):
            mesh = make_mesh(dp=8)
            with MeshScope(mesh), lock:
                out = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                                out_specs=P("dp"))(x)
            return out
        """)
    assert len(fired(fs, "spmd-axis-unknown")) == 1


def test_spmd_gate_sees_deliberate_collective_loops():
    """Non-vacuous proof the new family walks the real tree: the
    committed parallel/ package carries the deliberate per-leaf /
    ring-schedule collective loops as JUSTIFIED suppressions — visible,
    not invisible."""
    findings = analyze([REPO / "mxnet_tpu" / "parallel"], root=REPO,
                       use_cache=True)
    sup = [f for f in findings
           if f.rule == "spmd-collective-in-loop" and f.suppressed]
    assert len(sup) >= 5, [f.render() for f in findings]
    for f in sup:
        assert f.justification
    assert not [f for f in findings if not f.suppressed]


def test_registry_duplicate(tmp_path):
    fs = lint(tmp_path, """
        from mxnet_tpu.ops.registry import register_op, alias_op

        @register_op("my_op", aliases=("my_alias",))
        def _a(x):
            return x

        @register_op("my_op")            # BAD: shadows _a
        def _b(x):
            return x * 2

        alias_op("my_alias", "my_op")    # BAD: shadows the aliases= entry
        """)
    assert len(fired(fs, "registry-duplicate")) == 2


def test_registry_duplicate_clean(tmp_path):
    fs = lint(tmp_path, """
        from mxnet_tpu.ops.registry import register_op, alias_op

        @register_op("op_one", aliases=("one",))
        def _a(x):
            return x

        @register_op("op_two")
        def _b(x):
            return x * 2

        alias_op("two", "op_two")
        """)
    assert not fired(fs, "registry-duplicate")


def test_registry_missing_grad(tmp_path):
    fs = lint(tmp_path, """
        import functools
        import jax

        @jax.custom_vjp
        def broken(x):                   # BAD: no defvjp anywhere
            return x * 2

        @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
        def fine(x, axis):
            return x.sum(axis)

        def _fwd(x, axis):
            return fine(x, axis), x

        def _bwd(axis, res, g):
            return (g,)

        fine.defvjp(_fwd, _bwd)
        """)
    hits = fired(fs, "registry-missing-grad")
    assert len(hits) == 1 and "broken" in hits[0].message


def test_docs_stale_symbol(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "api.md").write_text(textwrap.dedent("""
        | Reference | Here |
        |---|---|
        | `mx.nd.reference_only_symbol` | `mx.io.RealThing` |
        | `something` | `mx.io.GhostIter` |
        | `path row` | `mxnet_tpu/missing_module.py` |
        | `other` | `real_module.py` helpers |

        Prose mentioning `vanished_callable()` and `RealThing.run()`.
        """))
    (tmp_path / "real_module.py").write_text(textwrap.dedent("""
        class RealThing:
            def run(self):
                return 1
        """))
    fs = analyze([tmp_path / "real_module.py"], root=tmp_path)
    stale = fired(fs, "docs-stale-symbol")
    assert len(stale) == 3, [f.message for f in stale]
    joined = " ".join(f.message for f in stale)
    assert "GhostIter" in joined
    assert "missing_module.py" in joined
    assert "vanished_callable" in joined
    # reference column + known symbols are never flagged
    assert "reference_only_symbol" not in joined
    assert "RealThing" not in joined


# ---------------------------------------------------------------------------
# engine mechanics
# ---------------------------------------------------------------------------

def test_bad_suppression_is_itself_a_finding(tmp_path):
    fs = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # mxlint: disable=trace-host-sync
        """)
    # no justification: the finding stays live AND the comment is flagged
    assert len(fired(fs, "trace-host-sync")) == 1
    assert len(fired(fs, BAD_SUPPRESSION)) == 1
    assert exit_code(fs) == 1


def test_standalone_suppression_comment_covers_next_line(tmp_path):
    fs = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            # mxlint: disable=trace-host-sync -- fixture: long-line form,
            # justification wraps over two comment lines
            return float(x)
        """)
    assert not fired(fs, "trace-host-sync")
    assert len(suppressed(fs, "trace-host-sync")) == 1


def test_config_disable_and_severity(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """
    off = lint(tmp_path, src, config=Config(disabled=["trace-host-sync"]))
    assert not [f for f in off if f.rule == "trace-host-sync"]
    warn = lint(tmp_path, src,
                config=Config(severities={"trace-host-sync": "warning"}))
    assert fired(warn, "trace-host-sync")[0].severity == "warning"
    assert exit_code(warn) == 0   # warnings do not gate
    with pytest.raises(ValueError):
        Config(severities={"trace-host-sync": "nope"})


def test_rule_ids_unique_and_documented():
    rules = default_rules()
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids))
    doc = (REPO / "docs" / "analysis.md").read_text()
    for rid in ids + [BAD_SUPPRESSION]:
        assert f"`{rid}`" in doc, f"docs/analysis.md missing rule {rid}"


def test_cli_json_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return x.item()
        """))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", str(bad), "--json",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload and payload[0]["rule"] == "trace-host-sync"
    clean = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO)
    assert clean.returncode == 0 and "trace-host-sync" in clean.stdout


# ---------------------------------------------------------------------------
# incremental cache + --changed (ISSUE 5 satellites)
# ---------------------------------------------------------------------------

_CACHE_BAD = """
    import jax

    @jax.jit
    def f(x):
        return x.item()
"""


def test_incremental_cache_roundtrip_and_invalidation(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(_CACHE_BAD))
    cold = analyze([p], root=tmp_path, use_cache=True)
    assert (tmp_path / ".mxlint_cache").is_dir(), \
        "cache directory never materialized"
    warm = analyze([p], root=tmp_path, use_cache=True)
    assert [f.to_dict() for f in cold] == [f.to_dict() for f in warm]
    assert len(fired(warm, "trace-host-sync")) == 1
    # content change invalidates: the fixed file must come back clean
    p.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return x * 2
        """))
    fixed = analyze([p], root=tmp_path, use_cache=True)
    assert not fired(fixed, "trace-host-sync")


def test_cache_records_carry_suppressions(tmp_path):
    # the suppression table rides in the cache record: a warm run must
    # report the same suppressed finding WITH its justification
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)  # mxlint: disable=trace-host-sync -- fixture: cached waiver
        """))
    analyze([p], root=tmp_path, use_cache=True)
    warm = analyze([p], root=tmp_path, use_cache=True)
    sup = suppressed(warm, "trace-host-sync")
    assert len(sup) == 1 and "cached waiver" in sup[0].justification


def test_cache_is_keyed_on_path_too(tmp_path):
    # identical content at two paths must not share one record: the
    # findings carry path anchors
    (tmp_path / "a.py").write_text(textwrap.dedent(_CACHE_BAD))
    (tmp_path / "b.py").write_text(textwrap.dedent(_CACHE_BAD))
    fs = analyze([tmp_path / "a.py", tmp_path / "b.py"], root=tmp_path,
                 use_cache=True)
    fs2 = analyze([tmp_path / "a.py", tmp_path / "b.py"], root=tmp_path,
                  use_cache=True)
    for run in (fs, fs2):
        assert sorted(f.path for f in fired(run, "trace-host-sync")) \
            == ["a.py", "b.py"]


def test_changed_only_filters_to_git_diff(tmp_path):
    import subprocess as sp

    def git(*args):
        return sp.run(["git", "-C", str(tmp_path), "-c",
                       "user.email=t@t", "-c", "user.name=t"] + list(args),
                      capture_output=True, text=True, check=True)

    (tmp_path / "stale.py").write_text(textwrap.dedent(_CACHE_BAD))
    (tmp_path / "fresh.py").write_text("x = 1\n")
    git("init")
    git("add", "-A")
    git("commit", "-m", "seed")
    # edit only fresh.py (now carrying a finding)
    (tmp_path / "fresh.py").write_text(textwrap.dedent(_CACHE_BAD))
    fs = analyze([tmp_path], root=tmp_path, changed_only=True)
    hit_paths = {f.path for f in fired(fs, "trace-host-sync")}
    assert hit_paths == {"fresh.py"}, \
        "expected only the git-changed file to be linted"
    # without the flag both fire
    full = analyze([tmp_path], root=tmp_path)
    assert {f.path for f in fired(full, "trace-host-sync")} \
        == {"stale.py", "fresh.py"}


def test_changed_only_with_root_below_git_toplevel(tmp_path):
    # git reports toplevel-relative names; linting a SUBPACKAGE with
    # --changed must still match them (regression: the intersection was
    # empty and the gate silently linted nothing)
    import subprocess as sp

    def git(*args):
        return sp.run(["git", "-C", str(tmp_path), "-c",
                       "user.email=t@t", "-c", "user.name=t"] + list(args),
                      capture_output=True, text=True, check=True)

    sub = tmp_path / "pkg"
    sub.mkdir()
    (sub / "mod.py").write_text("x = 1\n")
    git("init")
    git("add", "-A")
    git("commit", "-m", "seed")
    (sub / "mod.py").write_text(textwrap.dedent(_CACHE_BAD))
    fs = analyze([sub], root=sub, changed_only=True)
    assert len(fired(fs, "trace-host-sync")) == 1, \
        "changed file below a sub-root was silently skipped"


def test_cli_changed_default_paths_cover_gated_surface(tmp_path):
    # `python -m tools.analysis --changed --root X` with NO explicit
    # paths: the defaults are anchored at the root (not the cwd) and
    # span the gated surface, so an edited tools/ file is seen
    import subprocess as sp

    def git(*args):
        return sp.run(["git", "-C", str(tmp_path), "-c",
                       "user.email=t@t", "-c", "user.name=t"] + list(args),
                      capture_output=True, text=True, check=True)

    (tmp_path / "mxnet_tpu").mkdir()
    (tmp_path / "tools").mkdir()
    (tmp_path / "mxnet_tpu" / "ok.py").write_text("x = 1\n")
    (tmp_path / "tools" / "t.py").write_text("x = 1\n")
    git("init")
    git("add", "-A")
    git("commit", "-m", "seed")
    (tmp_path / "tools" / "t.py").write_text(textwrap.dedent(_CACHE_BAD))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--changed",
         "--root", str(tmp_path), "--no-cache", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert any(f["rule"] == "trace-host-sync"
               and f["path"].endswith("t.py") for f in payload), payload


def test_changed_only_fails_open_without_git(tmp_path):
    # no git repo: --changed must analyze everything rather than
    # silently narrowing to nothing
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(_CACHE_BAD))
    fs = analyze([p], root=tmp_path, changed_only=True)
    assert len(fired(fs, "trace-host-sync")) == 1


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------

def test_sarif_golden_envelope(tmp_path):
    """Golden-file contract for the SARIF envelope: CI annotation
    tooling parses this exact shape.  Regenerate the golden with
    ``python tests/goldens/regen_sarif.py`` after an intentional
    format/rule-metadata change."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            y = float(x)  # mxlint: disable=trace-host-sync -- golden: suppressed row
            return x.item()
        """))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", str(bad),
         "--format", "sarif", "--root", str(tmp_path), "--no-cache"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stderr
    golden = (REPO / "tests" / "goldens" / "mxlint_sarif.json").read_text()
    assert proc.stdout == golden, (
        "SARIF output drifted from tests/goldens/mxlint_sarif.json — "
        "if intentional, regenerate via tests/goldens/regen_sarif.py")
    log = json.loads(proc.stdout)
    run = log["runs"][0]
    assert log["version"] == "2.1.0"
    assert run["tool"]["driver"]["name"] == "mxlint"
    ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"trace-host-sync", "blocking-under-lock",
            "lock-order-inversion", "signal-handler-unsafe",
            "resource-leak-on-error"} <= ids
    results = run["results"]
    assert any(r["ruleId"] == "trace-host-sync"
               and r["locations"][0]["physicalLocation"]
               ["artifactLocation"]["uri"] == "bad.py"
               for r in results)
    # suppressed findings ride along as SARIF suppressions, not drops
    assert any(r.get("suppressions") for r in results)


def test_sarif_levels_map_severity(tmp_path):
    from tools.analysis import to_sarif
    fs = lint(tmp_path, _CACHE_BAD,
              config=Config(severities={"trace-host-sync": "warning"}))
    log = json.loads(to_sarif(fs))
    res = [r for r in log["runs"][0]["results"]
           if r["ruleId"] == "trace-host-sync"]
    assert res and res[0]["level"] == "warning"


# ---------------------------------------------------------------------------
# THE GATE: the shipped tree is clean (tier-1; ISSUE 3 acceptance,
# re-hosted on the CFG/dataflow engine by ISSUE 5 — the gate now also
# covers blocking-under-lock / lock-order-inversion /
# signal-handler-unsafe / resource-leak-on-error, and runs through the
# incremental cache so its wall-time stays flat as the suite grows)
# ---------------------------------------------------------------------------

def test_mxlint_self_check_gate():
    """``python -m tools.analysis mxnet_tpu/`` exits 0 on the shipped
    tree: zero unsuppressed findings, and every suppression that does
    exist carries a justification.  New code that breaks a trace/thread/
    donation/registry invariant fails HERE, in tier-1, not in review."""
    findings = analyze([REPO / "mxnet_tpu"], root=REPO, use_cache=True)
    live = [f for f in findings if not f.suppressed]
    assert not live, "mxlint findings on mxnet_tpu/:\n" + "\n".join(
        f.render() for f in live)
    for f in findings:
        if f.suppressed:
            assert f.justification, f.render()
    assert exit_code(findings) == 0


def test_mxlint_gate_covers_tools_and_bench():
    """The analysis package itself and the chip smoke stay clean too
    (the smoke constructs TrainStep feeds — donation hazards live
    there)."""
    findings = analyze([REPO / "tools" / "analysis",
                        REPO / "chip_smoke.py"],
                       root=REPO, use_cache=True)
    live = [f for f in findings if not f.suppressed]
    assert not live, "\n".join(f.render() for f in live)


def test_mxlint_gate_covers_examples():
    """examples/ is the code users copy: the concurrency/lifecycle suite
    gates it too (this caught real leaks — DataLoaders with producer
    machinery stranded on a mid-epoch crash — now fixed with the
    context-manager form the docs teach)."""
    findings = analyze([REPO / "examples"], root=REPO, use_cache=True)
    live = [f for f in findings if not f.suppressed]
    assert not live, "mxlint findings on examples/:\n" + "\n".join(
        f.render() for f in live)


def test_mxlint_gate_covers_serving():
    """mxnet_tpu/serving/ is inside the main gate's tree, but pin it
    explicitly: the DynamicBatcher is exactly the producer-thread /
    shared-attribute shape ``thread-unlocked-attr`` exists for, and this
    test is the proof the rule actually walks it (an empty module list
    would be a vacuous pass)."""
    from tools.analysis.core import _collect_files
    serving_dir = REPO / "mxnet_tpu" / "serving"
    files = _collect_files([serving_dir])
    assert any(f.name == "batcher.py" for f in files), \
        "serving package missing from the scan set"
    findings = analyze([serving_dir], root=REPO, use_cache=True)
    live = [f for f in findings if not f.suppressed]
    assert not live, "mxlint findings on mxnet_tpu/serving/:\n" + "\n".join(
        f.render() for f in live)
