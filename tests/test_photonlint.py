"""photonlint test suite (tier-1).

Four layers:
  1. per-rule positive/negative fixtures — each rule must flag its hazard
     and stay quiet on the idiomatic-correct twin;
  2. framework behaviour — suppression comments, baseline round-trip +
     --prune-baseline, parse-error surfacing, jit-index idiom resolution;
  3. whole-program resolution — a two-module fixture package where the
     jitted function and the violation live in different modules must be
     flagged with the ProgramIndex on and stay clean with
     ``--no-program-index``, incremental ``--paths`` runs must match the
     full run, and PL007 must see through the real repo's axis-name
     indirections (parallel/fixed.py against a shrunk mesh universe);
  4. the GATE: the full rule suite over ``photon_ml_tpu/`` must produce
     zero non-baselined violations and zero stale baseline entries (this
     is what makes every future PR lint-clean by construction), plus a CLI
     smoke test so ``python -m tools.photonlint`` and this test cannot
     drift apart.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.analysis import (analyze_source, build_rules,  # noqa: E402
                                    load_baseline, make_baseline, partition,
                                    registered_rules, run_analysis,
                                    save_baseline)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO_ROOT, "photon_ml_tpu")
BASELINE_PATH = os.path.join(REPO_ROOT, "photonlint_baseline.json")
HOT = "photon_ml_tpu/core/fixture.py"  # relpath inside dtype rule's scope


def lint(src, rule=None, path=HOT):
    rules = build_rules([rule]) if rule else build_rules()
    kept, _ = analyze_source(path, textwrap.dedent(src), rules)
    return kept


def suppressed(src, rule=None, path=HOT):
    rules = build_rules([rule]) if rule else build_rules()
    _, supp = analyze_source(path, textwrap.dedent(src), rules)
    return supp


# -- PL001 host-sync ---------------------------------------------------------

class TestHostSync:
    def test_positive_item_and_np_asarray_inside_jit(self):
        vs = lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                y = x.item()
                return np.asarray(y)
        """, "host-sync")
        assert len(vs) == 2
        assert all(v.rule == "host-sync" for v in vs)

    def test_positive_float_cast_of_param(self):
        vs = lint("""
            import jax

            @jax.jit
            def f(x):
                return float(x)
        """, "host-sync")
        assert len(vs) == 1 and "concretizes" in vs[0].message

    def test_positive_tolist_in_jit_wrapped_by_name(self):
        vs = lint("""
            import jax

            def solve(w):
                return w.tolist()

            fit = jax.jit(solve)
        """, "host-sync")
        assert len(vs) == 1 and ".tolist()" in vs[0].message

    def test_positive_print_of_param_is_warning(self):
        vs = lint("""
            import jax

            @jax.jit
            def f(x):
                print(x)
                return x
        """, "host-sync")
        assert len(vs) == 1 and vs[0].severity == "warning"

    def test_negative_outside_jit(self):
        assert lint("""
            import numpy as np

            def host_stats(x):
                return float(np.asarray(x).sum()), x.item()
        """, "host-sync") == []

    def test_negative_jnp_asarray_and_static_float(self):
        assert lint("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                n = x.shape[0]
                return jnp.asarray(x) * float(n)
        """, "host-sync") == []


# -- PL002 recompile-hazard --------------------------------------------------

class TestRecompileHazard:
    def test_positive_jit_in_loop(self):
        vs = lint("""
            import jax

            def sweep(fns, x):
                outs = []
                for fn in fns:
                    outs.append(jax.jit(fn))
                return outs
        """, "recompile-hazard")
        assert len(vs) == 1 and "inside a loop" in vs[0].message

    def test_positive_immediately_invoked_jit(self):
        vs = lint("""
            import jax

            def score(f, x):
                return jax.jit(f)(x)
        """, "recompile-hazard")
        assert len(vs) == 1 and "fresh" in vs[0].message

    def test_positive_dynamic_static_spec(self):
        vs = lint("""
            import jax

            def build(f, nums):
                return jax.jit(f, static_argnums=nums)
        """, "recompile-hazard")
        assert len(vs) == 1 and "static_argnums" in vs[0].message

    def test_negative_module_level_and_comprehension(self):
        # the build-once setup idioms of parallel/multihost.py
        assert lint("""
            import jax

            def f(x):
                return x

            g = jax.jit(f)
            table = {k: jax.jit(f, static_argnames=("n",)) for k in range(3)}
        """, "recompile-hazard") == []

    def test_negative_aot_bind_then_compile(self):
        # serving/engine.py: construct once per cache miss, then cache
        assert lint("""
            import jax

            def build(fn, args):
                jitted = jax.jit(fn)
                return jitted.lower(*args).compile()
        """, "recompile-hazard") == []


# -- PL003 tracer-safety -----------------------------------------------------

class TestTracerSafety:
    def test_positive_if_on_param(self):
        vs = lint("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """, "tracer-safety")
        assert len(vs) == 1 and "lax.cond" in vs[0].message

    def test_positive_while_and_iteration(self):
        vs = lint("""
            import jax

            @jax.jit
            def f(x):
                while x > 0:
                    x = x - 1
                for row in x:
                    pass
                return x
        """, "tracer-safety")
        assert {v.message.split()[0] for v in vs} == {"Python", "iterating"}

    def test_positive_ternary_and_assert(self):
        vs = lint("""
            import jax

            @jax.jit
            def f(x, y):
                assert y > 0
                return x if y > 0 else -x
        """, "tracer-safety")
        sev = sorted(v.severity for v in vs)
        assert sev == ["error", "warning"]

    def test_negative_static_tests(self):
        assert lint("""
            import jax

            @jax.jit
            def f(x, w=None):
                if w is None:
                    w = x
                if x.shape[0] > 2 and len(x) > 2:
                    w = w + 1
                return w
        """, "tracer-safety") == []

    def test_negative_static_argnames_param_exempt(self):
        assert lint("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                if n > 2:
                    return x * n
                return x
        """, "tracer-safety") == []


# -- PL004 dtype-discipline --------------------------------------------------

class TestDtypeDiscipline:
    def test_positive_f64_dtype_kwarg_and_attr(self):
        vs = lint("""
            import jax.numpy as jnp
            import numpy as np

            def init(n):
                a = jnp.zeros(n, dtype=np.float64)
                b = jnp.asarray([1.0], "float64")
                return a.astype(jnp.float64) + b
        """, "dtype-discipline")
        assert len(vs) == 3

    def test_positive_np_math_on_tracer(self):
        vs = lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.exp(x)
        """, "dtype-discipline")
        assert len(vs) == 1 and "jnp.exp" in vs[0].message

    def test_negative_host_numpy_f64_outside_jit(self):
        # normalization-statistics idiom: f64 accumulation is host-side
        assert lint("""
            import numpy as np

            def stats(values):
                return np.asarray(values, np.float64).sum()
        """, "dtype-discipline") == []

    def test_negative_out_of_scope_path(self):
        # storage codecs are host-side: f64 is the on-disk precision there
        assert lint("""
            import jax.numpy as jnp
            import numpy as np

            x = jnp.zeros(3, dtype=np.float64)
        """, "dtype-discipline",
                    path="photon_ml_tpu/storage/fixture.py") == []

    def test_negative_dtype_following(self):
        assert lint("""
            import jax.numpy as jnp

            def f(x):
                return jnp.zeros(x.shape, x.dtype)
        """, "dtype-discipline") == []


# -- PL005 lock-discipline ---------------------------------------------------

class TestLockDiscipline:
    def test_positive_unlocked_mutation_of_locked_attr(self):
        vs = lint("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def safe(self):
                    with self._lock:
                        self.n += 1

                def racy(self):
                    self.n += 1
        """, "lock-discipline")
        assert len(vs) == 1 and "data race" in vs[0].message
        assert vs[0].line == 14  # the mutation in racy()

    def test_positive_mutation_after_release(self):
        vs = lint("""
            import threading

            class Cache:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.entries = {}
                    self.count = 0

                def put(self, k, v):
                    with self._lock:
                        self.entries[k] = v
                    self.count += 1
        """, "lock-discipline")
        assert len(vs) == 1 and "outside it" in vs[0].message

    def test_negative_all_mutations_locked(self):
        assert lint("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                    self.items = []

                def bump(self):
                    with self._lock:
                        self.n += 1
                        self.items.append(self.n)
        """, "lock-discipline") == []

    def test_negative_class_without_lock(self):
        # single-threaded classes are out of scope by design
        assert lint("""
            class Accum:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1
        """, "lock-discipline") == []

    def test_negative_init_exempt(self):
        assert lint("""
            import threading

            class C:
                def __init__(self, n):
                    self._lock = threading.Lock()
                    self.n = n

                def set(self, n):
                    with self._lock:
                        self.n = n
        """, "lock-discipline") == []

    # -- the PL005 blind spots found while building the ProgramIndex --------

    def test_positive_operator_module_mutation(self):
        vs = lint("""
            import operator
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def safe(self):
                    with self._lock:
                        self.items.append(1)

                def racy(self):
                    operator.iadd(self.items, [2])
        """, "lock-discipline")
        assert len(vs) == 1 and "data race" in vs[0].message

    def test_positive_operator_alias_setitem(self):
        vs = lint("""
            import operator as op
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.d = {}

                def safe(self, k, v):
                    with self._lock:
                        self.d[k] = v

                def racy(self, k, v):
                    op.setitem(self.d, k, v)
        """, "lock-discipline")
        assert len(vs) == 1

    def test_positive_from_operator_import(self):
        vs = lint("""
            import threading
            from operator import iadd

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def safe(self):
                    with self._lock:
                        self.items.extend([0])

                def racy(self):
                    iadd(self.items, [1])
        """, "lock-discipline")
        assert len(vs) == 1

    def test_positive_starred_unpack_target(self):
        vs = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.head = None
                    self.rest = []

                def safe(self, xs):
                    with self._lock:
                        self.head, *self.rest = xs

                def racy(self, xs):
                    self.head, *self.rest = xs
        """, "lock-discipline")
        assert len(vs) == 2  # head AND the starred rest slot

    def test_negative_operator_mutation_under_lock(self):
        assert lint("""
            import operator
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def safe(self):
                    with self._lock:
                        operator.iadd(self.items, [1])
        """, "lock-discipline") == []


# -- PL006 donation-after-use ------------------------------------------------

class TestDonation:
    def test_positive_read_after_donating_call(self):
        vs = lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def caller(v):
                buf = make()
                out = f(buf, v)
                return buf * 2
        """, "donation-after-use")
        assert len(vs) == 1 and "use-after-free" in vs[0].message
        assert "buf" in vs[0].message and vs[0].severity == "error"

    def test_positive_donate_argnames_keyword(self):
        vs = lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnames=("buf",))

            def caller(v):
                b = make()
                out = f(buf=b, v=v)
                return b.sum()
        """, "donation-after-use")
        assert len(vs) == 1 and "`b`" in vs[0].message

    def test_positive_aot_chain_donor(self):
        # serving/engine.py's jit().lower().compile() executable idiom
        vs = lint("""
            import jax

            def kernel(buf, w):
                return buf @ w

            exe = jax.jit(kernel, donate_argnums=(0,)).lower(x, w).compile()

            def score(w):
                req = stage()
                out = exe(req, w)
                return req
        """, "donation-after-use")
        assert len(vs) == 1

    def test_positive_reuse_across_loop_iterations(self):
        vs = lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def caller(vs):
                buf = make()
                acc = []
                for v in vs:
                    acc.append(f(buf, v))
                return acc
        """, "donation-after-use")
        assert len(vs) == 1  # iteration 2 reads the buffer donated in 1

    def test_positive_conditional_donate_spec(self):
        # engine.py's backend-gated spec: both IfExp branches contribute
        vs = lint("""
            import jax

            def update(buf, v):
                return buf + v

            donate = (0,) if accelerated else ()
            f = jax.jit(update, donate_argnums=donate)

            def caller(v):
                buf = make()
                out = f(buf, v)
                return buf
        """, "donation-after-use")
        assert len(vs) == 1

    def test_positive_param_donation_is_warning(self):
        vs = lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def helper(buf, v):
                return f(buf, v)
        """, "donation-after-use")
        assert len(vs) == 1 and vs[0].severity == "warning"
        assert "crosses the function boundary" in vs[0].message

    def test_negative_rebind_idiom(self):
        # transfer.py's sanctioned pattern: out = donating(out, ...)
        assert lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def caller(vs):
                buf = make()
                for v in vs:
                    buf = f(buf, v)
                return buf
        """, "donation-after-use") == []

    def test_negative_no_donation(self):
        assert lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update)

            def caller(v):
                buf = make()
                out = f(buf, v)
                return buf
        """, "donation-after-use") == []

    def test_negative_read_before_donate(self):
        assert lint("""
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def caller(v):
                buf = make()
                checksum = buf.sum()
                out = f(buf, v)
                return out, checksum
        """, "donation-after-use") == []


# -- PL007 mesh-axis ----------------------------------------------------------

class TestMeshAxis:
    def test_positive_shard_map_site_mesh(self):
        vs = lint("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P

            mesh = Mesh(devices, ("data", "model"))

            def run(w, b):
                def local(w, b):
                    return jax.lax.psum(w, "batch")
                return jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                                     out_specs=P())(w, b)
        """, "mesh-axis")
        assert len(vs) == 1
        assert "'batch'" in vs[0].message and "data" in vs[0].message

    def test_positive_universe_fallback(self):
        # no shard_map binding resolvable: validate against every Mesh in
        # the module (the --no-program-index fallback)
        vs = lint("""
            import jax
            from jax.sharding import Mesh

            mesh = Mesh(devices, ("data",))

            def local(w):
                return jax.lax.psum(w, "feature")
        """, "mesh-axis")
        assert len(vs) == 1 and "no Mesh in the program" in vs[0].message

    def test_positive_axis_via_constant_chain(self):
        # the repo idiom: axis name constant -> parameter default -> use
        vs = lint("""
            import jax
            from jax.sharding import Mesh

            ROWS = "rows"
            mesh = Mesh(devices, (ROWS,))

            class Obj:
                def __init__(self, axis="cols"):
                    self.axis = axis

                def value(self, w):
                    obj, axis = self, self.axis
                    return jax.lax.psum(w, axis)
        """, "mesh-axis")
        assert len(vs) == 1 and "'cols'" in vs[0].message

    def test_negative_valid_axes(self):
        assert lint("""
            import jax
            from jax.sharding import Mesh

            AXIS = "rows"
            mesh = Mesh(devices, (AXIS, "cols"))

            def run(w):
                def local(w):
                    i = jax.lax.axis_index(AXIS)
                    return jax.lax.psum(w, "cols") + i
                return jax.shard_map(local, mesh=mesh)(w)
        """, "mesh-axis") == []

    def test_negative_unresolvable_axis_stays_quiet(self):
        assert lint("""
            import jax
            from jax.sharding import Mesh

            mesh = Mesh(devices, ("data",))

            def run(w, axis):
                return jax.lax.psum(w, axis)
        """, "mesh-axis") == []

    def test_negative_no_mesh_anywhere(self):
        assert lint("""
            import jax

            def local(w):
                return jax.lax.psum(w, "anything")
        """, "mesh-axis") == []


# -- PL008 sharding-annotation ------------------------------------------------

PARALLEL = "photon_ml_tpu/parallel/fixture.py"


class TestShardingAnnotation:
    def test_positive_unannotated_jit_on_mesh_path(self):
        vs = lint("""
            import jax

            def solve(w, b):
                return w

            fitted = jax.jit(solve)
        """, "sharding-annotation", path=PARALLEL)
        assert len(vs) == 1 and vs[0].severity == "warning"
        assert "out_shardings" in vs[0].message

    def test_positive_unannotated_decorators(self):
        vs = lint("""
            import functools
            import jax

            @jax.jit
            def a(w):
                return w

            @functools.partial(jax.jit, static_argnames=("n",))
            def b(w, n):
                return w * n
        """, "sharding-annotation", path=PARALLEL)
        assert len(vs) == 2

    def test_negative_annotated_or_off_mesh_path(self):
        assert lint("""
            import functools
            import jax

            @functools.partial(jax.jit, out_shardings=None)
            def a(w):
                return w

            fitted = jax.jit(a, out_shardings=rep)
        """, "sharding-annotation", path=PARALLEL) == []
        # serving/ etc. never trip the annotation warning
        assert lint("""
            import jax

            fitted = jax.jit(lambda w: w)
        """, "sharding-annotation",
                    path="photon_ml_tpu/serving/fixture.py") == []

    def test_positive_namedsharding_axis_not_on_paired_mesh(self):
        vs = lint("""
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devices, ("data", "model"))
            s = NamedSharding(mesh, P("feature"))
        """, "sharding-annotation")
        assert len(vs) == 1
        assert "'feature'" in vs[0].message and "paired" in vs[0].message

    def test_positive_bare_pspec_against_universe(self):
        vs = lint("""
            from jax.sharding import Mesh, PartitionSpec as P

            mesh = Mesh(devices, ("data",))
            spec = P("model")
        """, "sharding-annotation")
        assert len(vs) == 1 and "no Mesh in the program" in vs[0].message

    def test_negative_valid_specs_and_unresolvable(self):
        assert lint("""
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            AXIS = "data"
            mesh = Mesh(devices, (AXIS, "model"))
            a = NamedSharding(mesh, P(AXIS))
            b = NamedSharding(mesh, P(("data", "model")))
            c = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            d = P(AXIS, None)

            def row_spec(arr):
                return P(AXIS, *([None] * (arr.ndim - 1)))
        """, "sharding-annotation") == []


# -- whole-program (cross-module) resolution ----------------------------------

def _write_pkg(tmp_path, files):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    return str(tmp_path)


CROSS_HELPER = """
    def helper(x):
        return x.item()
"""

CROSS_MAIN = """
    import jax

    from pkg.helper import helper

    fit = jax.jit(helper)
"""


class TestCrossModuleResolution:
    def _run(self, root, whole_program=True, index_paths=None, paths=None):
        return run_analysis(paths or [os.path.join(root, "pkg")],
                            root=root, whole_program=whole_program,
                            index_paths=index_paths)

    def test_jitted_in_another_module_is_flagged(self, tmp_path):
        """THE tentpole acceptance fixture: function defined in helper.py,
        jitted in main.py — flagged whole-program, clean per-module."""
        root = _write_pkg(tmp_path, {"helper.py": CROSS_HELPER,
                                     "main.py": CROSS_MAIN})
        res = self._run(root)
        assert [v.rule for v in res.violations] == ["host-sync"]
        assert res.violations[0].path == "pkg/helper.py"
        assert self._run(root, whole_program=False).violations == []

    def test_module_alias_jit_target(self, tmp_path):
        root = _write_pkg(tmp_path, {
            "helper.py": CROSS_HELPER,
            "main.py": """
                import jax

                import pkg.helper as h

                fit = jax.jit(h.helper)
            """,
        })
        res = self._run(root)
        assert [v.rule for v in res.violations] == ["host-sync"]

    def test_call_graph_propagation_across_modules(self, tmp_path):
        # helper is never jitted directly — it's CALLED from jitted code in
        # another module; tracer-safety must still fire on it
        root = _write_pkg(tmp_path, {
            "helper.py": """
                def clamp(x):
                    if x > 0:
                        return x
                    return -x
            """,
            "main.py": """
                import jax

                from pkg.helper import clamp

                @jax.jit
                def entry(x):
                    return clamp(x) + 1
            """,
        })
        res = self._run(root)
        assert [v.rule for v in res.violations] == ["tracer-safety"]
        assert res.violations[0].path == "pkg/helper.py"
        assert self._run(root, whole_program=False).violations == []

    def test_incremental_paths_match_full_run(self, tmp_path):
        # lint ONLY helper.py; the jit site lives in main.py, so the
        # finding exists iff the index covers the whole package
        root = _write_pkg(tmp_path, {"helper.py": CROSS_HELPER,
                                     "main.py": CROSS_MAIN})
        helper = os.path.join(root, "pkg", "helper.py")
        full = self._run(root)
        inc = self._run(root, paths=[helper],
                        index_paths=[os.path.join(root, "pkg")])
        assert ([v.fingerprint() for v in inc.violations]
                == [v.fingerprint() for v in full.violations])
        # without the package-wide index the violation is invisible
        assert self._run(root, paths=[helper]).violations == []

    def test_cross_module_axis_constants(self, tmp_path):
        # PL007 resolves the axis constant AND the mesh through the
        # ProgramIndex: the collective and the Mesh live in different files
        root = _write_pkg(tmp_path, {
            "meshes.py": """
                from jax.sharding import Mesh

                DATA = "data"
                mesh = Mesh(devices, (DATA, "entity"))
            """,
            "obj.py": """
                import jax

                from pkg.meshes import DATA

                def local(w):
                    return jax.lax.psum(w, DATA) + jax.lax.psum(w, "feature")
            """,
        })
        res = self._run(root)
        msgs = [v.message for v in res.violations]
        assert len(msgs) == 1 and "'feature'" in msgs[0]
        # per-module mode: obj.py has no mesh in sight -> quiet
        assert self._run(root, whole_program=False).violations == []

    def test_cli_no_program_index_escape_hatch(self, tmp_path):
        root = _write_pkg(tmp_path, {"helper.py": CROSS_HELPER,
                                     "main.py": CROSS_MAIN})
        base = [sys.executable, "-m", "tools.photonlint",
                os.path.join(root, "pkg"), "--no-baseline", "--root", root]
        on = subprocess.run(base, cwd=REPO_ROOT, capture_output=True,
                            text=True, timeout=300)
        off = subprocess.run(base + ["--no-program-index"], cwd=REPO_ROOT,
                             capture_output=True, text=True, timeout=300)
        assert on.returncode == 1 and "host-sync" in on.stdout
        assert off.returncode == 0, off.stdout + off.stderr

    def test_pl007_sees_through_real_fixed_py(self):
        """Real-repo demonstration: parallel/fixed.py's psum sites resolve
        their axis names through self.feature_axis -> parameter default ->
        the FEATURE_AXIS constant imported from parallel/mesh.py.  Linted
        against a program whose meshes LACK the feature axis, those sites
        must light up; against the real package they are clean."""
        from photon_ml_tpu.analysis.program_index import ProgramIndex

        fixed_rel = "photon_ml_tpu/parallel/fixed.py"
        with open(os.path.join(REPO_ROOT, fixed_rel), encoding="utf-8") as f:
            fixed_src = f.read()
        shrunk_mesh = textwrap.dedent("""
            from jax.sharding import Mesh

            DATA_AXIS = "data"
            ENTITY_AXIS = "entity"
            FEATURE_AXIS = "feature"

            def padded_dim(d, mesh, axis=FEATURE_AXIS):
                return d

            def replicate(mesh):
                return None

            def shard_batch(batch, mesh, axis=DATA_AXIS, feature_axis=None):
                return batch

            def shard_coefficients(w, mesh, axis=FEATURE_AXIS):
                return w

            mesh = Mesh(devices, (DATA_AXIS, ENTITY_AXIS))
        """)
        program = ProgramIndex({fixed_rel: fixed_src,
                                "photon_ml_tpu/parallel/mesh.py": shrunk_mesh})
        assert program.axis_universe == {"data", "entity"}
        kept, _ = analyze_source(fixed_rel, fixed_src,
                                 build_rules(["mesh-axis"]), program=program)
        assert len(kept) >= 3  # the feature-axis psum/axis_index sites
        assert all("'feature'" in v.message for v in kept)
        # and the real package's universe keeps them clean (the gate
        # re-checks this over every rule)
        full = ProgramIndex.from_paths(
            [os.path.join(REPO_ROOT, "photon_ml_tpu")], REPO_ROOT)
        assert {"data", "entity", "feature"} <= full.axis_universe
        kept2, _ = analyze_source(fixed_rel, fixed_src,
                                  build_rules(["mesh-axis"]), program=full)
        assert kept2 == []


# -- PL009 swallowed-exception -----------------------------------------------

class TestSwallowedException:
    def test_positive_thread_target_method(self):
        vs = lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()

                def _run(self):
                    while True:
                        try:
                            self.step()
                        except Exception:
                            pass
        """, "swallowed-exception")
        assert len(vs) == 1 and vs[0].rule == "swallowed-exception"
        assert "detached" in vs[0].message

    def test_positive_async_def_body(self):
        vs = lint("""
            async def pump(q):
                while True:
                    try:
                        await q.drain()
                    except Exception:
                        continue
        """, "swallowed-exception")
        assert len(vs) == 1

    def test_positive_tuple_containing_exception(self):
        vs = lint("""
            import threading

            def run():
                try:
                    work()
                except (ValueError, Exception):
                    pass

            threading.Thread(target=run).start()
        """, "swallowed-exception")
        assert len(vs) == 1

    def test_positive_bare_except(self):
        vs = lint("""
            async def loop():
                try:
                    step()
                except:
                    pass
        """, "swallowed-exception")
        assert len(vs) == 1

    def test_negative_logging_counts_as_handled(self):
        assert lint("""
            import logging
            logger = logging.getLogger(__name__)

            async def loop():
                try:
                    step()
                except Exception:
                    logger.exception("step failed")
        """, "swallowed-exception") == []

    def test_negative_metric_increment_counts_as_handled(self):
        assert lint("""
            async def loop(registry):
                try:
                    step()
                except Exception:
                    registry.inc("step_errors_total")
        """, "swallowed-exception") == []

    def test_negative_bound_name_use_counts_as_handled(self):
        assert lint("""
            async def loop(self):
                try:
                    step()
                except Exception as e:
                    self.last_error = e
        """, "swallowed-exception") == []

    def test_negative_reraise_counts_as_handled(self):
        assert lint("""
            async def loop():
                try:
                    step()
                except Exception:
                    raise
        """, "swallowed-exception") == []

    def test_negative_cleanup_only_try_exempt(self):
        assert lint("""
            async def close(writer):
                try:
                    writer.close()
                except Exception:
                    pass
        """, "swallowed-exception") == []

    def test_negative_not_a_thread_target(self):
        # same swallow, but the function runs on the request path where a
        # raise IS observed — out of scope
        assert lint("""
            def helper():
                try:
                    work()
                except Exception:
                    pass
        """, "swallowed-exception") == []

    def test_negative_narrow_except_out_of_scope(self):
        assert lint("""
            async def loop():
                try:
                    step()
                except ValueError:
                    pass
        """, "swallowed-exception") == []

    def test_suppression_comment_works(self):
        src = """
            import threading

            def run():
                try:
                    work()
                except Exception:  # photonlint: disable=swallowed-exception -- fire drill
                    pass

            threading.Thread(target=run).start()
        """
        assert lint(src, "swallowed-exception") == []
        assert len(suppressed(src, "swallowed-exception")) == 1


# -- PL010 span-discipline ----------------------------------------------------

class TestSpanDiscipline:
    def test_positive_discarded_span_call(self):
        vs = lint("""
            from photon_ml_tpu.obs.trace import span

            def f():
                span("op", bucket=64)
                work()
        """, "span-discipline")
        assert len(vs) == 1 and vs[0].rule == "span-discipline"
        assert "discarded" in vs[0].message

    def test_positive_escaping_handle(self):
        vs = lint("""
            from photon_ml_tpu.obs.trace import span

            def begin():
                h = span("op")
                return h
        """, "span-discipline")
        assert len(vs) == 1 and "escapes" in vs[0].message

    def test_positive_enter_without_exit(self):
        vs = lint("""
            from photon_ml_tpu.obs.trace import span

            def f():
                h = span("op")
                h.__enter__()
                work()
        """, "span-discipline")
        assert len(vs) == 1 and "__enter__" in vs[0].message

    def test_positive_method_call_counts(self):
        # Tracer.span via an instance is the same contract
        vs = lint("""
            def f(tracer):
                tracer.span("op")
        """, "span-discipline")
        assert len(vs) == 1 and "discarded" in vs[0].message

    def test_negative_with_block_and_as_handle(self):
        assert lint("""
            from photon_ml_tpu.obs.trace import span

            def f():
                with span("op", bucket=64):
                    work()
                with span("op2") as h:
                    h  # the handle is usable inside the block
        """, "span-discipline") == []

    def test_negative_handle_used_as_with_item(self):
        assert lint("""
            from photon_ml_tpu.obs.trace import span

            def f():
                h = span("op")
                with h:
                    work()
        """, "span-discipline") == []

    def test_negative_balanced_manual_enter_exit(self):
        assert lint("""
            from photon_ml_tpu.obs.trace import span

            def f():
                h = span("op")
                h.__enter__()
                try:
                    work()
                finally:
                    h.__exit__(None, None, None)
        """, "span-discipline") == []

    def test_negative_non_span_enter_ignored(self):
        # a lock entered manually is not a span handle — out of scope
        assert lint("""
            def f(lock):
                lock.__enter__()
                work()
        """, "span-discipline") == []

    def test_negative_provider_module_exempt(self):
        # the module DEFINING span() is the tracer implementation
        assert lint("""
            def span(name, **attrs):
                return _Span(name, attrs)

            def helper():
                s = span("x")
                return s
        """, "span-discipline") == []


# -- suppressions ------------------------------------------------------------

SUPPRESSIBLE = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:  {inline}
            return x
        return -x
"""


class TestSuppressions:
    def test_same_line_disable(self):
        src = SUPPRESSIBLE.format(
            inline="# photonlint: disable=tracer-safety -- fixture")
        assert lint(src, "tracer-safety") == []
        assert len(suppressed(src, "tracer-safety")) == 1

    def test_comment_above_disable(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                # photonlint: disable=tracer-safety -- reason spanning
                # a second comment line before the statement
                if x > 0:
                    return x
                return -x
        """
        assert lint(src, "tracer-safety") == []

    def test_disable_all(self):
        src = SUPPRESSIBLE.format(inline="# photonlint: disable=all")
        assert lint(src, "tracer-safety") == []

    def test_unrelated_rule_does_not_suppress(self):
        src = SUPPRESSIBLE.format(inline="# photonlint: disable=host-sync")
        assert len(lint(src, "tracer-safety")) == 1

    def test_disable_file(self):
        src = ("# photonlint: disable-file=tracer-safety\n"
               + textwrap.dedent(SUPPRESSIBLE.format(inline="")))
        assert lint(src, "tracer-safety") == []

    def test_new_rules_suppress_like_any_other(self):
        donated = """
            import jax

            def update(buf, v):
                return buf + v

            f = jax.jit(update, donate_argnums=(0,))

            def caller(v):
                buf = make()
                out = f(buf, v)
                return buf  {inline}
        """
        flagged = donated.format(inline="")
        assert len(lint(flagged, "donation-after-use")) == 1
        quiet = donated.format(
            inline="# photonlint: disable=donation-after-use -- fixture")
        assert lint(quiet, "donation-after-use") == []
        assert len(suppressed(quiet, "donation-after-use")) == 1


# -- baseline ----------------------------------------------------------------

RACY = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            self.m = 0

        def safe(self):
            with self._lock:
                self.n += 1
                self.m += 1

        def racy_n(self):
            self.n += 1
"""

RACY_EXTRA = """
        def racy_m(self):
            self.m += 2
"""


class TestBaseline:
    def _violations(self, extra=""):
        return lint(textwrap.dedent(RACY + extra), "lock-discipline")

    def test_round_trip_baselined_passes_new_fails(self, tmp_path):
        vs = self._violations()
        assert len(vs) == 1
        path = str(tmp_path / "baseline.json")
        save_baseline(make_baseline(vs), path)
        loaded = load_baseline(path)
        new, matched, stale = partition(vs, loaded)
        assert new == [] and len(matched) == 1 and stale == []
        # a NEW violation (different attribute) is not absorbed
        vs2 = self._violations(extra=RACY_EXTRA)
        assert len(vs2) == 2
        new2, matched2, _ = partition(vs2, loaded)
        assert len(new2) == 1 and len(matched2) == 1
        assert "m" in new2[0].snippet

    def test_stale_entries_reported(self, tmp_path):
        vs = self._violations()
        baseline = make_baseline(vs)
        baseline["entries"]["deadbeefdeadbeef"] = {"rule": "host-sync"}
        path = str(tmp_path / "baseline.json")
        save_baseline(baseline, path)
        new, matched, stale = partition(vs, load_baseline(path))
        assert new == [] and stale == ["deadbeefdeadbeef"]

    def test_fingerprint_survives_line_shift(self):
        vs1 = self._violations()
        shifted = ("# a new leading comment\n\n"
                   + textwrap.dedent(RACY))
        vs2 = lint(shifted, "lock-discipline")
        assert len(vs2) == 1
        assert vs1[0].fingerprint() == vs2[0].fingerprint()
        assert vs1[0].line != vs2[0].line

    def test_new_rules_round_trip(self, tmp_path):
        # PL007 findings baseline and re-match like any PL001-era rule
        src = """
            import jax
            from jax.sharding import Mesh

            mesh = Mesh(devices, ("data",))

            def local(w):
                return jax.lax.psum(w, "feature")
        """
        vs = lint(src, "mesh-axis")
        assert len(vs) == 1
        path = str(tmp_path / "baseline.json")
        save_baseline(make_baseline(vs), path)
        new, matched, stale = partition(vs, load_baseline(path))
        assert new == [] and len(matched) == 1 and stale == []


BAD_FIXTURE = """
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""


class TestPruneBaseline:
    """The --prune-baseline workflow: stale fingerprints (debt that no
    source line produces any more) FAIL the gate by default and are
    auto-removed with the flag — paid-down debt cannot silently linger."""

    def _cli(self, args):
        return subprocess.run(
            [sys.executable, "-m", "tools.photonlint"] + args,
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)

    def test_stale_entry_fails_then_prunes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_FIXTURE)
        baseline = str(tmp_path / "baseline.json")
        root = str(tmp_path)
        base_args = [str(bad), "--baseline", baseline, "--root", root]
        # 1. baseline the real finding -> gate goes green
        assert self._cli(base_args + ["--write-baseline"]).returncode == 0
        assert self._cli(base_args).returncode == 0
        # 2. plant a fingerprint no source line matches
        data = json.loads(open(baseline).read())
        real_fps = set(data["entries"])
        data["entries"]["feedfacefeedface"] = {
            "rule": "tracer-safety", "code": "PL003", "path": "bad.py",
            "message": "long-gone finding", "snippet": "gone", "occurrence": 0}
        with open(baseline, "w") as f:
            json.dump(data, f)
        # 3. stale entry -> exit 1 (the default is strict)
        proc = self._cli(base_args)
        assert proc.returncode == 1 and "stale" in proc.stdout
        # 4. --prune-baseline removes it, keeps live debt, exits 0
        assert self._cli(base_args + ["--prune-baseline"]).returncode == 0
        pruned = json.loads(open(baseline).read())
        assert set(pruned["entries"]) == real_fps
        assert self._cli(base_args).returncode == 0

    def test_incremental_run_does_not_misjudge_other_files(self, tmp_path):
        # an entry for a file OUTSIDE an incremental --paths run must not
        # be reported stale (the run can't vouch for files it didn't lint)
        bad = tmp_path / "bad.py"
        bad.write_text(BAD_FIXTURE)
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        baseline = str(tmp_path / "baseline.json")
        root = str(tmp_path)
        assert self._cli([str(bad), "--baseline", baseline, "--root", root,
                          "--write-baseline"]).returncode == 0
        proc = self._cli(["--paths", str(clean), "--baseline", baseline,
                          "--root", root])
        assert proc.returncode == 0, proc.stdout + proc.stderr


# -- framework odds and ends -------------------------------------------------

class TestFramework:
    def test_parse_error_is_a_violation(self):
        vs = lint("def broken(:\n")
        assert len(vs) == 1 and vs[0].rule == "parse-error"

    def test_rule_catalog_registered(self):
        registry = registered_rules()
        assert set(registry) >= {"host-sync", "recompile-hazard",
                                 "tracer-safety", "dtype-discipline",
                                 "lock-discipline", "donation-after-use",
                                 "mesh-axis", "sharding-annotation"}
        assert len(registry) >= 8

    def test_unknown_rule_rejected(self):
        with pytest.raises(KeyError):
            build_rules(["no-such-rule"])

    def test_jit_index_resolves_vmap_sandwich(self):
        vs = lint("""
            import jax

            def kernel(w):
                return float(w)

            vk = jax.jit(jax.vmap(kernel))
        """, "host-sync")
        assert len(vs) == 1

    def test_jit_index_resolves_lambda(self):
        vs = lint("""
            import jax
            import numpy as np

            score = jax.jit(lambda w: np.asarray(w))
        """, "host-sync")
        assert len(vs) == 1


# -- the tier-1 gate ---------------------------------------------------------

class TestPackageGate:
    def test_package_has_no_new_violations(self):
        """THE gate: every future PR must keep photon_ml_tpu/ lint-clean
        (or explicitly baseline/suppress with a reason) — in whole-program
        mode, which run_analysis defaults to."""
        result = run_analysis([PKG_DIR], root=REPO_ROOT)
        assert result.whole_program  # cross-module resolution is the default
        baseline = load_baseline(BASELINE_PATH)
        new, _, stale = partition(result.violations, baseline)
        assert not new, (
            "new photonlint violations (fix, suppress with a reason, or "
            "baseline):\n" + "\n".join(v.render() for v in new))
        assert not stale, (
            "stale baseline entries (debt paid down but still recorded) — "
            f"prune with --prune-baseline: {stale}")

    def test_committed_baseline_is_empty(self):
        # the repo carries NO accepted lint debt; keep it that way
        assert load_baseline(BASELINE_PATH)["entries"] == {}

    def test_gate_scans_the_whole_package(self):
        result = run_analysis([PKG_DIR], root=REPO_ROOT)
        assert result.files_scanned >= 100  # the package, not a subset
        # the analysis-cost budget: the whole-program pass must stay a
        # pre-commit-friendly few seconds (acceptance: < 10 s on CPU);
        # index build is the new cost and must stay a fraction of that
        assert result.index_build_s < 5.0

    def test_cli_exit_zero_on_package(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.photonlint",
             os.path.join(REPO_ROOT, "photon_ml_tpu")],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_cli_json_and_nonzero_on_violation(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """))
        proc = subprocess.run(
            [sys.executable, "-m", "tools.photonlint", str(bad),
             "--no-baseline", "--format", "json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload["summary"]["new"] == 1
        assert payload["new"][0]["rule"] == "tracer-safety"
        # the CI-facing summary block: per-rule/severity counts + scan costs
        summary = payload["summary"]
        assert summary["by_rule"] == {"tracer-safety": 1}
        assert summary["by_severity"] == {"error": 1}
        assert summary["files_scanned"] == 1
        assert summary["whole_program"] is True
        assert isinstance(summary["index_build_s"], float)

    def test_whole_package_gate_stays_inside_its_budget(self):
        """The gate as CI runs it (script, default scope, the committed
        baseline): clean, and the whole-program pass inside the 10 s it was
        given.  The budget is held against the child's CPU seconds: the
        suite's workers share the cores, and the pass is one thread."""
        import resource

        before = resource.getrusage(resource.RUSAGE_CHILDREN)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "photonlint.py"),
             "--format", "json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        after = resource.getrusage(resource.RUSAGE_CHILDREN)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        summary = json.loads(proc.stdout)["summary"]
        assert summary["new"] == summary["baselined"] == summary["stale"] == 0
        assert summary["files_scanned"] >= 100 and summary["whole_program"]
        cpu_s = ((after.ru_utime + after.ru_stime)
                 - (before.ru_utime + before.ru_stime))
        assert 0 < cpu_s < 10
        # the three accounted shares sit inside the whole
        for share in ("index_build_s", "dataflow_s", "summaries_s"):
            assert 0 <= summary[share] < cpu_s
