"""staticcheck engine + rule coverage.

Three layers per new rule: trigger on a fixture (exactly one finding with
the expected code — the seeded self-check), suppression via ``# noqa``, and
suppression via the committed-baseline mechanism. Engine features (noqa
span resolution, ``# noqa-file`` pragma, baseline semantics, json output)
get their own cases. The legacy rule set keeps its coverage in
tests/test_lint.py against the CLI shim.
"""

import io
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cuda_mpi_gpu_cluster_programming_tpu.staticcheck import engine  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.engine import (  # noqa: E402
    baseline_payload,
    check_files,
    split_by_baseline,
)


def findings_for(path: Path, code: str = None):
    out, _ = check_files([path])
    return [f for f in out if code is None or f.code == code]


def run_engine(paths, baseline_path=None, fmt="text", update=False):
    buf = io.StringIO()
    rc = engine.run(
        paths, baseline_path=baseline_path, fmt=fmt,
        update_baseline=update, out=buf,
    )
    return rc, buf.getvalue()


# ---------------------------------------------------------------------------
# rule fixtures: (filename, source, expected-code, expected-line)

_WRONG_AXIS = (
    "wrongaxis.py",
    "from jax import lax, shard_map\n"
    "from jax.sharding import PartitionSpec as P\n"
    "def body(x):\n"
    "    return lax.psum(x, 'dp')\n"          # mesh below binds only 'sp'
    "def build(mesh):\n"
    "    return shard_map(body, mesh=mesh, in_specs=(P('sp'),),\n"
    "                     out_specs=P('sp'))\n",
    "collective-axis",
    4,
)
_UNREDUCED = (
    "unreduced.py",
    "import jax.numpy as jnp\n"
    "from jax import shard_map\n"
    "from jax.sharding import PartitionSpec as P\n"
    "def body(a, b):\n"
    "    return jnp.matmul(a, b)\n"
    "def build(mesh):\n"
    "    return shard_map(body, mesh=mesh,\n"
    "                     in_specs=(P(None, 'tp'), P('tp', None)),\n"
    "                     out_specs=P())\n",
    "unreduced-contraction",
    7,
)
_HOST_SYNC = (
    "harness.py",  # the rule is scoped to the measurement surfaces by name
    "import time\n"
    "def measure(fn, x, steps):\n"
    "    times = []\n"
    "    for _ in range(steps):\n"
    "        t0 = time.perf_counter()\n"
    "        out = float(fn(x))\n"
    "        times.append(time.perf_counter() - t0)\n"
    "    return times, out\n",
    "host-sync-in-hot-loop",
    6,
)
_KEY_REUSE = (
    "keyreuse.py",
    "import jax\n"
    "def draws():\n"
    "    key = jax.random.PRNGKey(0)\n"
    "    a = jax.random.normal(key, (4,))\n"
    "    b = jax.random.normal(key, (4,))\n"
    "    return a, b\n",
    "key-reuse",
    5,
)
_JIT_IN_LOOP = (
    "jitloop.py",
    "import jax\n"
    "def sweep(fns, x):\n"
    "    outs = []\n"
    "    for fn in fns:\n"
    "        outs.append(jax.jit(fn)(x))\n"
    "    return outs\n",
    "jit-in-loop",
    5,
)
_VMA_OFF = (
    "vmaoff.py",
    "from jax import shard_map\n"
    "from jax.sharding import PartitionSpec as P\n"
    "def build(body, mesh):\n"
    "    return shard_map(body, mesh=mesh, in_specs=(P('sp'),),\n"
    "                     out_specs=P('sp'), check_vma=False)\n",
    "check-vma-disabled",
    5,
)
_STALE_DEVICES = (
    "staledev.py",
    "import jax\n"
    "from jax.sharding import Mesh\n"
    "DEVICES = jax.devices()\n"        # cached at import: stale by rebuild
    "def rebuild(n):\n"
    "    return Mesh(DEVICES[:n], ('sp',))\n",
    "stale-device-set",
    5,
)
ALL_FIXTURES = [
    _WRONG_AXIS, _UNREDUCED, _HOST_SYNC, _KEY_REUSE, _JIT_IN_LOOP, _VMA_OFF,
    _STALE_DEVICES,
]


@pytest.mark.parametrize(
    "name,src,code,line", ALL_FIXTURES, ids=[f[2] for f in ALL_FIXTURES]
)
def test_rule_triggers_exactly_once(tmp_path, name, src, code, line):
    """The seeded self-check: each planted bug yields exactly ONE finding
    with the expected code, at the expected line."""
    p = tmp_path / name
    p.write_text(src)
    got = findings_for(p, code)
    assert len(got) == 1, [f"{f.code}@{f.line}" for f in findings_for(p)]
    assert got[0].line == line
    assert got[0].severity == "error"


@pytest.mark.parametrize(
    "name,src,code,line", ALL_FIXTURES, ids=[f[2] for f in ALL_FIXTURES]
)
def test_rule_suppressed_by_noqa(tmp_path, name, src, code, line):
    lines = src.splitlines()
    lines[line - 1] += f"  # noqa: {code} deliberate (with a reason)"
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    assert findings_for(p, code) == []


@pytest.mark.parametrize(
    "name,src,code,line", ALL_FIXTURES, ids=[f[2] for f in ALL_FIXTURES]
)
def test_rule_grandfathered_by_baseline(tmp_path, name, src, code, line):
    p = tmp_path / name
    p.write_text(src)
    all_findings = findings_for(p)
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps(baseline_payload(all_findings, ROOT)))
    rc, out = run_engine([p], baseline_path=bp)
    assert rc == 0, out
    assert f"[{code}]" not in out
    assert f"{len(all_findings)} baselined" in out


# ---------------------------------------------------------------------------
# negatives: working idioms must NOT be flagged


def test_collective_axis_bound_via_module_constant(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text(
        "from jax import lax, shard_map\n"
        "from jax.sharding import PartitionSpec as P\n"
        "AXIS = 'sp'\n"
        "def body(x):\n"
        "    return lax.psum(x, AXIS)\n"
        "def build(mesh):\n"
        "    return shard_map(body, mesh=mesh, in_specs=(P(None, AXIS),),\n"
        "                     out_specs=P(None, AXIS))\n"
    )
    assert findings_for(p, "collective-axis") == []


def test_collective_axis_dynamic_name_not_judged(tmp_path):
    # A variable axis (halo.py-style helper taking axis_name) is not
    # statically resolvable: never flagged.
    p = tmp_path / "helper.py"
    p.write_text(
        "from jax import lax\n"
        "def exchange(x, axis_name):\n"
        "    return lax.ppermute(x, axis_name, [(0, 1)])\n"
    )
    assert findings_for(p, "collective-axis") == []


def test_unreduced_contraction_ok_with_psum_or_out_axis(tmp_path):
    base = (
        "import jax.numpy as jnp\n"
        "from jax import lax, shard_map\n"
        "from jax.sharding import PartitionSpec as P\n"
        "def body(a, b):\n"
        "    return {ret}\n"
        "def build(mesh):\n"
        "    return shard_map(body, mesh=mesh,\n"
        "                     in_specs=(P(None, 'tp'), P('tp', None)),\n"
        "                     out_specs={out})\n"
    )
    psum = tmp_path / "with_psum.py"
    psum.write_text(base.format(ret="lax.psum(jnp.matmul(a, b), 'tp')", out="P()"))
    assert findings_for(psum, "unreduced-contraction") == []
    kept = tmp_path / "axis_kept.py"
    kept.write_text(base.format(ret="jnp.matmul(a, b)", out="P(None, 'tp')"))
    assert findings_for(kept, "unreduced-contraction") == []


def test_host_sync_scoping(tmp_path):
    src = (
        "import time\n"
        "def f(rows):\n"
        "    for r in rows:\n"
        "        t0 = time.monotonic()\n"
        "        x = float(r)\n"
        "        _ = time.monotonic() - t0\n"
        "    return x\n"
    )
    # Same code outside the measurement surfaces: not in scope.
    other = tmp_path / "parsing.py"
    other.write_text(src)
    assert findings_for(other, "host-sync-in-hot-loop") == []
    # float() in an UNtimed loop (row parsing) is not flagged even in scope.
    untimed = tmp_path / "harness.py"
    untimed.write_text(
        "def f(rows):\n"
        "    out = [0.0]\n"
        "    for r in rows:\n"
        "        out.append(float(r))\n"
        "    return out\n"
    )
    assert findings_for(untimed, "host-sync-in-hot-loop") == []
    # .item() is a sync regardless of timing calls.
    item = tmp_path / "training.py"
    item.write_text(
        "def f(losses):\n"
        "    total = 0.0\n"
        "    for l in losses:\n"
        "        total += l.item()\n"
        "    return total\n"
    )
    assert len(findings_for(item, "host-sync-in-hot-loop")) == 1


def test_host_sync_off_timed_path_exemption(tmp_path):
    """The in-graph sentinel contract: digest screening inside a function
    decorated @off_timed_path is exempt (it is a host round trip BY DESIGN,
    between timed regions); the same sync undecorated still trips. Both in
    supervisor.py, which the rule now scopes alongside run.py."""
    f = tmp_path / "supervisor.py"
    f.write_text(
        "import numpy as np\n"
        "from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import (\n"
        "    off_timed_path,\n"
        ")\n"
        "@off_timed_path\n"
        "def screen(digests):\n"
        "    out = {}\n"
        "    for stage, vec in digests.items():\n"
        "        out[stage] = np.asarray(vec)\n"
        "    return out\n"
        "def hot(digests):\n"
        "    out = {}\n"
        "    for stage, vec in digests.items():\n"
        "        out[stage] = np.asarray(vec)\n"
        "    return out\n"
    )
    found = findings_for(f, "host-sync-in-hot-loop")
    assert len(found) == 1
    assert found[0].line == 14  # the undecorated copy only
    assert "off_timed_path" in found[0].message


def test_host_sync_scope_includes_run_and_supervisor():
    """run.py and resilience/supervisor.py are measurement surfaces now —
    and the shipped code stays clean under the grown scope (the repo-clean
    assertion for the in-graph taps)."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        HostSyncInHotLoopRule,
        _HOT_LOOP_FILES,
    )

    assert {"run.py", "supervisor.py"} <= _HOT_LOOP_FILES
    rule = HostSyncInHotLoopRule()
    assert rule.applies(Path("cuda_mpi_gpu_cluster_programming_tpu/run.py"))
    for rel in (
        "cuda_mpi_gpu_cluster_programming_tpu/run.py",
        "cuda_mpi_gpu_cluster_programming_tpu/resilience/supervisor.py",
        "cuda_mpi_gpu_cluster_programming_tpu/resilience/sentinel.py",
    ):
        assert findings_for(ROOT / rel, "host-sync-in-hot-loop") == []


def test_host_sync_scope_includes_serving_dispatch_loop(tmp_path):
    """ISSUE 6 satellite: the serving subsystem's dispatch/load loops are
    hot paths — a host sync per dispatched batch is a latency tax on every
    request — so serving/{server,loadgen,batcher,queue}.py are in scope,
    the shipped modules stay clean, and the @off_timed_path exemption
    (journal writes / result slicing) works there exactly as it does for
    the supervisor's screening."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        HostSyncInHotLoopRule,
        _HOT_LOOP_FILES,
    )

    assert {"server.py", "loadgen.py", "batcher.py", "queue.py"} <= _HOT_LOOP_FILES
    rule = HostSyncInHotLoopRule()
    assert rule.applies(
        Path("cuda_mpi_gpu_cluster_programming_tpu/serving/server.py")
    )
    for rel in (
        "cuda_mpi_gpu_cluster_programming_tpu/serving/server.py",
        "cuda_mpi_gpu_cluster_programming_tpu/serving/loadgen.py",
        "cuda_mpi_gpu_cluster_programming_tpu/serving/batcher.py",
        "cuda_mpi_gpu_cluster_programming_tpu/serving/queue.py",
    ):
        assert findings_for(ROOT / rel, "host-sync-in-hot-loop") == []
    # a sync in a dispatch loop IS flagged in a serving-named file...
    bad = tmp_path / "server.py"
    bad.write_text(
        "import numpy as np\n"
        "def loop(batches, fwd):\n"
        "    outs = []\n"
        "    for b in batches:\n"
        "        outs.append(np.asarray(fwd(b)))\n"
        "    return outs\n"
    )
    assert len(findings_for(bad, "host-sync-in-hot-loop")) == 1
    # ...and the same sync under @off_timed_path (journal/completion
    # writes) is exempt, per the existing annotation contract.
    ok = tmp_path / "loadgen.py"
    ok.write_text(
        "import numpy as np\n"
        "from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel "
        "import off_timed_path\n"
        "@off_timed_path\n"
        "def complete(batches):\n"
        "    outs = []\n"
        "    for b in batches:\n"
        "        outs.append(np.asarray(b))\n"
        "    return outs\n"
    )
    assert findings_for(ok, "host-sync-in-hot-loop") == []


def test_host_sync_scope_includes_controller(tmp_path):
    """ISSUE 18 satellite: the Autopilot controller is evaluated from the
    dispatch loop's observation cadence every tick, so
    serving/controller.py joins the hot-loop scope — the shipped module
    stays clean (actuation rides @off_timed_path), a sync in an
    undecorated controller loop is flagged, and the decorated copy is
    exempt."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        HostSyncInHotLoopRule,
        _HOT_LOOP_FILES,
    )

    assert "controller.py" in _HOT_LOOP_FILES
    rule = HostSyncInHotLoopRule()
    assert rule.applies(
        Path("cuda_mpi_gpu_cluster_programming_tpu/serving/controller.py")
    )
    assert findings_for(
        ROOT / "cuda_mpi_gpu_cluster_programming_tpu/serving/controller.py",
        "host-sync-in-hot-loop",
    ) == []
    bad = tmp_path / "controller.py"
    bad.write_text(
        "import numpy as np\n"
        "def evaluate(windows, fwd):\n"
        "    burns = []\n"
        "    for w in windows:\n"
        "        burns.append(np.asarray(fwd(w)))\n"
        "    return burns\n"
    )
    assert len(findings_for(bad, "host-sync-in-hot-loop")) == 1
    (tmp_path / "ok").mkdir()
    ok = tmp_path / "ok" / "controller.py"
    ok.write_text(
        "import numpy as np\n"
        "from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel "
        "import off_timed_path\n"
        "@off_timed_path\n"
        "def screen(windows):\n"
        "    burns = []\n"
        "    for w in windows:\n"
        "        burns.append(np.asarray(w))\n"
        "    return burns\n"
    )
    assert findings_for(ok, "host-sync-in-hot-loop") == []


def test_key_reuse_split_and_branches_ok(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(
        "import jax\n"
        "def draws(flag):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    k1, k2 = jax.random.split(key)\n"
        "    a = jax.random.normal(k1, (4,))\n"
        "    b = jax.random.normal(k2, (4,))\n"
        "    if flag:\n"
        "        c = jax.random.normal(b, (4,))\n"
        "    else:\n"
        "        c = jax.random.normal(b, (4,))\n"  # exclusive branch: fine
        "    return a, c\n"
    )
    assert findings_for(ok, "key-reuse") == []


def test_key_reuse_loop_invariant_key(tmp_path):
    p = tmp_path / "loop.py"
    p.write_text(
        "import jax\n"
        "def gen(n):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    out = []\n"
        "    for _ in range(n):\n"
        "        out.append(jax.random.normal(key, (4,)))\n"
        "    return out\n"
    )
    assert len(findings_for(p, "key-reuse")) == 1
    ok = tmp_path / "loop_ok.py"
    ok.write_text(
        "import jax\n"
        "def gen(n):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    out = []\n"
        "    for _ in range(n):\n"
        "        key, sub = jax.random.split(key)\n"
        "        out.append(jax.random.normal(sub, (4,)))\n"
        "    return out\n"
    )
    assert findings_for(ok, "key-reuse") == []


def test_jit_in_loop_hoisted_ok(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text(
        "import jax\n"
        "def sweep(fn, xs):\n"
        "    jfn = jax.jit(fn)\n"
        "    return [jfn(x) for x in xs]\n"
    )
    assert findings_for(p, "jit-in-loop") == []


def test_check_vma_computed_value_ok(tmp_path):
    # check_vma=kernel_check_vma() (the sanctioned pattern) is not a
    # literal False: never flagged.
    p = tmp_path / "ok.py"
    p.write_text(
        "from jax import shard_map\n"
        "from jax.sharding import PartitionSpec as P\n"
        "def build(body, mesh, flag):\n"
        "    return shard_map(body, mesh=mesh, in_specs=(P('sp'),),\n"
        "                     out_specs=P('sp'), check_vma=flag)\n"
    )
    assert findings_for(p, "check-vma-disabled") == []


def test_stale_device_set_requery_and_module_scope_ok(tmp_path):
    """The sanctioned patterns stay silent: re-querying jax.devices() at
    build time inside the function, and a module-scope mesh build (runs at
    import, when the cached list is still fresh)."""
    p = tmp_path / "ok.py"
    p.write_text(
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "DEVICES = jax.devices()\n"
        "TOP_MESH = Mesh(DEVICES, ('sp',))\n"   # import-time: fresh
        "def rebuild(n):\n"
        "    return Mesh(jax.devices()[:n], ('sp',))\n"  # re-query: fresh
        "def helper(devs, n):\n"
        "    return Mesh(devs[:n], ('sp',))\n"  # caller-supplied: not judged
    )
    assert findings_for(p, "stale-device-set") == []


def test_stale_device_set_make_mesh_kwarg_and_list_wrap(tmp_path):
    """make_mesh(devices=CACHED) and list(jax.devices()) caches are the
    same bug in different spelling — both flagged."""
    p = tmp_path / "kw.py"
    p.write_text(
        "import jax\n"
        "from cuda_mpi_gpu_cluster_programming_tpu.parallel.mesh import make_mesh\n"
        "ALL = list(jax.devices())\n"
        "def retry_build(n):\n"
        "    return make_mesh(n, devices=ALL)\n"
    )
    found = findings_for(p, "stale-device-set")
    assert [f.line for f in found] == [5]
    assert "re-query" in found[0].message


def test_stale_device_set_annotated_module_cache_flagged(tmp_path):
    """ISSUE 10: the annotated spelling of the module cache
    (``DEVICES: list = jax.devices()``) is the same stale-device bug —
    flagged like the bare assignment."""
    p = tmp_path / "ann.py"
    p.write_text(
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "DEVICES: list = jax.devices()\n"
        "def rebuild(n):\n"
        "    return Mesh(DEVICES[:n], ('sp',))\n"
    )
    found = findings_for(p, "stale-device-set")
    assert [f.line for f in found] == [5]
    assert "DEVICES" in found[0].message


def test_implicit_upcast_triggers_in_hot_path_dirs(tmp_path):
    """ISSUE 7 satellite: a contraction over bf16/int8-cast operands with
    no explicit preferred_element_type, in a hot-path module, is flagged —
    inline casts and name-bound casts alike."""
    d = tmp_path / "ops"
    d.mkdir()
    p = d / "hot.py"
    p.write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def mix(x, w):\n"
        "    return jnp.dot(x.astype(jnp.bfloat16), w)\n"
        "def bound(x, w):\n"
        "    xb = x.astype(jnp.int8)\n"
        "    return lax.dot_general(xb, w, (((1,), (0,)), ((), ())))\n"
    )
    found = findings_for(p, "implicit-upcast")
    assert [f.line for f in found] == [4, 7]
    assert all("preferred_element_type" in f.message for f in found)


def test_implicit_upcast_explicit_accumulate_ok(tmp_path):
    """Stating the accumulation dtype (the precision-subsystem contract)
    silences the rule; fp32-only contractions are never judged."""
    d = tmp_path / "precision"
    d.mkdir()
    p = d / "quantize.py"
    p.write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def stated(x, w):\n"
        "    return jnp.dot(x.astype(jnp.bfloat16), w,\n"
        "                   preferred_element_type=jnp.float32)\n"
        "def fp32_only(x, w):\n"
        "    return jnp.dot(x.astype(jnp.float32), w)\n"
        "def unknown_dtypes(x, w):\n"
        "    return jnp.dot(x, w)\n"
    )
    assert findings_for(p, "implicit-upcast") == []


def test_implicit_upcast_scoping_and_noqa(tmp_path):
    """Out of the hot-path dirs (ops/models/parallel/precision) the rule is
    silent; in scope, # noqa documents a deliberate inference."""
    src = (
        "import jax.numpy as jnp\n"
        "def mix(x, w):\n"
        "    return jnp.dot(x.astype(jnp.bfloat16), w)\n"
    )
    cold = tmp_path / "analysis.py"
    cold.write_text(src)
    assert findings_for(cold, "implicit-upcast") == []
    d = tmp_path / "models"
    d.mkdir()
    hot = d / "net.py"
    hot.write_text(src.replace(", w)", ", w)  # noqa: implicit-upcast"))
    assert findings_for(hot, "implicit-upcast") == []


def test_implicit_upcast_repo_hot_paths_clean():
    """The shipped mixed-precision code states its accumulation dtype: the
    rule's own scope stays 0-findings (the baseline stays empty)."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        ImplicitUpcastRule,
    )

    rule = ImplicitUpcastRule()
    assert rule.applies(
        Path("cuda_mpi_gpu_cluster_programming_tpu/precision/quantize.py")
    )
    assert not rule.applies(Path("cuda_mpi_gpu_cluster_programming_tpu/run.py"))
    pkg = ROOT / "cuda_mpi_gpu_cluster_programming_tpu"
    files = [
        f
        for sub in ("ops", "models", "parallel", "precision")
        for f in sorted((pkg / sub).glob("*.py"))
    ]
    assert files
    assert [f for f in files if findings_for(f, "implicit-upcast")] == []


# ---------------------------------------------------------------------------
# engine features


def test_noqa_resolves_over_statement_span(tmp_path):
    """The historical false-positive: a multi-line construct whose finding
    reports one line while the # noqa sits on another line of the same
    statement. Both directions must suppress."""
    p = tmp_path / "span.py"
    p.write_text(
        "def f(\n"
        "    a,\n"
        "    b=[],\n"
        "):  # noqa: mutable-default\n"
        "    return a, b\n"
    )
    assert findings_for(p, "mutable-default") == []
    # raw-subprocess on a multi-line call, noqa on the closing line.
    q = tmp_path / "scripts" / "multi.py"
    q.parent.mkdir()
    q.write_text(
        "import subprocess\n"
        "subprocess.run(\n"
        "    ['true'],\n"
        ")  # noqa: raw-subprocess\n"
    )
    assert findings_for(q, "raw-subprocess") == []
    # Control: without the annotation both fire.
    r = tmp_path / "scripts" / "bare.py"
    r.write_text("import subprocess\nsubprocess.run(\n    ['true'],\n)\n")
    assert len(findings_for(r, "raw-subprocess")) == 1


def test_noqa_file_pragma(tmp_path):
    body = (
        "import jax\n"
        "def draws():\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    a = jax.random.normal(key, (4,))\n"
        "    b = jax.random.normal(key, (4,))\n"
        "    return a, b\n"
    )
    p = tmp_path / "gen.py"
    p.write_text("# generated file\n# noqa-file: key-reuse\n" + body)
    assert findings_for(p, "key-reuse") == []
    # The pragma only counts in the first 5 lines.
    q = tmp_path / "late.py"
    q.write_text(body + "# noqa-file: key-reuse\n")
    assert len(findings_for(q, "key-reuse")) == 1
    # Bare pragma suppresses everything.
    r = tmp_path / "all.py"
    r.write_text("# noqa-file\n" + body + "import os\n")
    assert findings_for(r) == []


def test_baseline_counts_allow_old_fail_new(tmp_path):
    p = tmp_path / "keyreuse.py"
    p.write_text(_KEY_REUSE[1])
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps(baseline_payload(findings_for(p), ROOT)))
    rc, _ = run_engine([p], baseline_path=bp)
    assert rc == 0
    # A SECOND reuse in the same file exceeds the grandfathered count: the
    # extra finding (and only it) fails the run.
    p.write_text(
        _KEY_REUSE[1].replace(
            "    return a, b\n",
            "    c = jax.random.normal(key, (4,))\n    return a, b, c\n",
        )
    )
    rc, out = run_engine([p], baseline_path=bp)
    assert rc == 1
    assert out.count("[key-reuse]") == 1
    assert "1 baselined" in out


def test_baseline_update_roundtrip(tmp_path):
    p = tmp_path / "keyreuse.py"
    p.write_text(_KEY_REUSE[1])
    bp = tmp_path / "baseline.json"
    rc, _ = run_engine([p], baseline_path=bp, update=True)
    assert rc == 0 and bp.exists()
    data = json.loads(bp.read_text())
    assert data["version"] == 1
    assert list(data["entries"].values()) == [{"key-reuse": 1}]
    rc, _ = run_engine([p], baseline_path=bp)
    assert rc == 0


def test_split_by_baseline_orders_by_line(tmp_path):
    p = tmp_path / "keyreuse.py"
    p.write_text(
        _KEY_REUSE[1].replace(
            "    return a, b\n",
            "    c = jax.random.normal(key, (4,))\n    return a, b, c\n",
        )
    )
    found = findings_for(p, "key-reuse")
    assert len(found) == 2
    baseline = {engine.baseline_key(p, ROOT): {"key-reuse": 1}}
    new, old = split_by_baseline(found, baseline, ROOT)
    assert [f.line for f in old] == [5]  # earliest finding grandfathered
    assert [f.line for f in new] == [6]


def test_json_format(tmp_path):
    p = tmp_path / "keyreuse.py"
    p.write_text(_KEY_REUSE[1])
    rc, out = run_engine([p], fmt="json")
    assert rc == 1
    data = json.loads(out)
    assert data["files"] == 1
    assert data["grandfathered"] == []
    (f,) = data["new"]
    assert f["code"] == "key-reuse" and f["line"] == 5
    assert f["severity"] == "error"


def test_syntax_error_single_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    got = findings_for(p)
    assert len(got) == 1 and got[0].code == "syntax"


def test_cli_module_entry_on_fixture(tmp_path):
    p = tmp_path / "keyreuse.py"
    p.write_text(_KEY_REUSE[1])
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.staticcheck",
            "--no-baseline", str(p),
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=60,
    )
    assert proc.returncode == 1
    assert "[key-reuse]" in proc.stdout


def test_cli_list_rules_has_all_new_codes():
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.staticcheck",
            "--list-rules",
        ],
        capture_output=True, text=True, cwd=ROOT, timeout=60,
    )
    assert proc.returncode == 0
    for code in (
        "collective-axis", "unreduced-contraction", "host-sync-in-hot-loop",
        "key-reuse", "jit-in-loop", "check-vma-disabled", "implicit-upcast",
        "stale-device-set", "span-write-in-timed-region",
        "blocking-socket-call-in-timed-region",
        "raw-subprocess", "atomic-write", "deprecated",
    ):
        assert code in proc.stdout, code


# ---------------------------------------------------------------------------
# span-write-in-timed-region (ISSUE 9) + observability host-sync scope


_SPAN_WRITE_SRC = (
    "import time\n"
    "def loop(tracer, reg, batches, fwd):\n"
    "    for b in batches:\n"
    "        t0 = time.perf_counter()\n"
    "        out = fwd(b)\n"
    "        ms = (time.perf_counter() - t0) * 1e3\n"
    "        reg.histogram('batch_ms').observe(ms)\n"  # line 7: flagged
    "    return out\n"
)


def test_span_write_in_timed_region_triggers(tmp_path):
    """A metric observation inside a timed dispatch loop is flagged in a
    hot-loop-scoped file (here: a serving-named fixture)."""
    p = tmp_path / "server.py"
    p.write_text(_SPAN_WRITE_SRC)
    found = findings_for(p, "span-write-in-timed-region")
    assert len(found) == 1 and found[0].line == 7
    assert "off_timed_path" in found[0].message


def test_span_write_covers_emit_and_span_ctx(tmp_path):
    p = tmp_path / "loadgen.py"
    p.write_text(
        "import time\n"
        "from cuda_mpi_gpu_cluster_programming_tpu.observability.trace import span\n"
        "def loop(tracer, xs):\n"
        "    while xs:\n"
        "        t0 = time.monotonic()\n"
        "        with span('dispatch'):\n"      # line 6: flagged (ctx form)
        "            xs.pop()\n"
        "        tracer.emit('x', t0, time.monotonic())\n"  # line 8: flagged
    )
    found = findings_for(p, "span-write-in-timed-region")
    assert sorted(f.line for f in found) == [6, 8]


def test_span_write_untimed_loop_and_off_timed_path_exempt(tmp_path):
    """Only TIMED regions are in scope, and @off_timed_path persistence
    helpers are exempt by contract — the serving completion path."""
    p = tmp_path / "server.py"
    p.write_text(
        "import time\n"
        "def off_timed_path(fn):\n"
        "    return fn\n"
        "def drain(reg, batches):\n"
        "    for b in batches:\n"          # no clock read: not a timed region
        "        reg.counter('ok').inc()\n"
        "@off_timed_path\n"
        "def complete(tracer, reg, batches):\n"
        "    for b in batches:\n"
        "        t0 = time.perf_counter()\n"
        "        reg.histogram('ms').observe(time.perf_counter() - t0)\n"
        "        tracer.emit('dispatch', t0, time.perf_counter())\n"
    )
    assert findings_for(p, "span-write-in-timed-region") == []


def test_span_write_noqa(tmp_path):
    p = tmp_path / "server.py"
    src = _SPAN_WRITE_SRC.replace(
        ".observe(ms)\n", ".observe(ms)  # noqa: span-write-in-timed-region\n"
    )
    p.write_text(src)
    assert findings_for(p, "span-write-in-timed-region") == []


def test_observability_scope_and_shipped_modules_clean():
    """ISSUE 9 satellite: observability/ joins the host-sync scope (an
    instrumentation layer that syncs inside the loops it instruments
    corrupts what it reports), the new span-write rule covers it, and the
    shipped modules are clean under both rules."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        HostSyncInHotLoopRule,
        SpanWriteInTimedRegionRule,
    )

    obs = "cuda_mpi_gpu_cluster_programming_tpu/observability"
    for rule in (HostSyncInHotLoopRule(), SpanWriteInTimedRegionRule()):
        assert rule.applies(Path(f"{obs}/trace.py"))
        assert rule.applies(Path("cuda_mpi_gpu_cluster_programming_tpu/run.py"))
        assert not rule.applies(
            Path("cuda_mpi_gpu_cluster_programming_tpu/analysis.py")
        )
    # ISSUE 12/13/15: the directory scope grows with the subsystem — the
    # replay pacing loop (a timed loop re-driving a recorded arrival
    # schedule), the roofline/specs modules, and the fleet
    # health analyzer are covered the moment they exist, and ship clean.
    for mod in (
        "trace.py", "metrics.py", "stages.py", "export.py",
        "replay.py", "roofline.py", "specs.py", "health.py",
    ):
        for rule in (HostSyncInHotLoopRule(), SpanWriteInTimedRegionRule()):
            assert rule.applies(Path(f"{obs}/{mod}"))
        assert findings_for(ROOT / obs / mod, "host-sync-in-hot-loop") == []
        assert findings_for(ROOT / obs / mod, "span-write-in-timed-region") == []
    # the wired hot paths stay clean too (persistence lives in
    # @off_timed_path helpers by construction)
    for rel in (
        "cuda_mpi_gpu_cluster_programming_tpu/serving/server.py",
        "cuda_mpi_gpu_cluster_programming_tpu/resilience/supervisor.py",
    ):
        assert findings_for(ROOT / rel, "span-write-in-timed-region") == []


# ---------------------------------------------------------------------------
# blocking-socket-call-in-timed-region (ISSUE 11) + frontend hot-loop scope


_SOCKET_SRC = (
    "import time\n"
    "def pump(sock, batches):\n"
    "    for b in batches:\n"
    "        t0 = time.perf_counter()\n"
    "        data = sock.recv(4096)\n"  # line 5: flagged
    "        ms = (time.perf_counter() - t0) * 1e3\n"
    "    return ms\n"
)


def test_blocking_socket_in_timed_region_triggers(tmp_path):
    """A socket recv inside a timed dispatch loop is flagged in a
    hot-loop-scoped file (here: a frontend-named fixture)."""
    p = tmp_path / "frontend.py"
    p.write_text(_SOCKET_SRC)
    found = findings_for(p, "blocking-socket-call-in-timed-region")
    assert len(found) == 1 and found[0].line == 5
    assert "off_timed_path" in found[0].message


def test_blocking_socket_covers_client_calls(tmp_path):
    p = tmp_path / "loadgen.py"
    p.write_text(
        "import time\n"
        "from urllib.request import urlopen\n"
        "def fleet(conn, urls):\n"
        "    while urls:\n"
        "        t0 = time.monotonic()\n"
        "        conn.connect()\n"                 # line 6: flagged
        "        resp = conn.getresponse()\n"      # line 7: flagged
        "        urlopen(urls.pop())\n"            # line 8: flagged
        "        dt = time.monotonic() - t0\n"
    )
    found = findings_for(p, "blocking-socket-call-in-timed-region")
    assert sorted(f.line for f in found) == [6, 7, 8]


def test_blocking_socket_untimed_loop_off_timed_path_and_noqa(tmp_path):
    """Only TIMED regions are in scope; @off_timed_path transport helpers
    are exempt by contract; a deliberate latency-measuring client loop
    carries a reviewed # noqa."""
    p = tmp_path / "frontend.py"
    p.write_text(
        "import time\n"
        "def off_timed_path(fn):\n"
        "    return fn\n"
        "def drain(sock, batches):\n"
        "    for b in batches:\n"          # no clock read: not a timed region
        "        sock.sendall(b)\n"
        "@off_timed_path\n"
        "def transport(sock, batches):\n"
        "    for b in batches:\n"
        "        t0 = time.monotonic()\n"
        "        sock.sendall(b)\n"
        "        data = sock.recv(4096)\n"
        "        dt = time.monotonic() - t0\n"
    )
    assert findings_for(p, "blocking-socket-call-in-timed-region") == []
    q = tmp_path / "server.py"
    q.write_text(
        _SOCKET_SRC.replace(
            ".recv(4096)\n",
            ".recv(4096)  # noqa: blocking-socket-call-in-timed-region\n",
        )
    )
    assert findings_for(q, "blocking-socket-call-in-timed-region") == []


def test_blocking_socket_scope_and_shipped_serving_clean():
    """ISSUE 11 satellite: the serving front end + traffic/SLO layers join
    the hot-loop scope, and the shipped modules are clean under both the
    host-sync and blocking-socket rules (the client fleet's one
    deliberate socket wait carries its reviewed # noqa)."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        BlockingSocketInTimedRegionRule,
        HostSyncInHotLoopRule,
    )

    serving = "cuda_mpi_gpu_cluster_programming_tpu/serving"
    for rule in (HostSyncInHotLoopRule(), BlockingSocketInTimedRegionRule()):
        assert rule.applies(Path(f"{serving}/frontend.py"))
        assert rule.applies(Path(f"{serving}/traffic.py"))
        assert rule.applies(Path(f"{serving}/slo.py"))
        assert not rule.applies(
            Path("cuda_mpi_gpu_cluster_programming_tpu/analysis.py")
        )
    for mod in ("frontend.py", "traffic.py", "slo.py", "server.py", "loadgen.py"):
        assert findings_for(ROOT / serving / mod, "host-sync-in-hot-loop") == []
        assert findings_for(
            ROOT / serving / mod, "blocking-socket-call-in-timed-region"
        ) == []


def test_router_tier_in_hot_loop_scope_and_clean():
    """ISSUE 16 satellite: the fleet router tier (serving/router.py +
    serving/fleet.py) joins the hot-loop scope — its probe/forward waits
    are timed regions — and ships clean: the deliberate socket waits
    (the probe IS the health measurement; the hop wait IS the redirect
    budget) carry their reviewed # noqa."""
    from cuda_mpi_gpu_cluster_programming_tpu.staticcheck.rules_jax import (
        BlockingSocketInTimedRegionRule,
        HostSyncInHotLoopRule,
    )

    serving = "cuda_mpi_gpu_cluster_programming_tpu/serving"
    for rule in (HostSyncInHotLoopRule(), BlockingSocketInTimedRegionRule()):
        assert rule.applies(Path(f"{serving}/router.py"))
        assert rule.applies(Path(f"{serving}/fleet.py"))
    for mod in ("router.py", "fleet.py"):
        assert findings_for(ROOT / serving / mod, "host-sync-in-hot-loop") == []
        assert findings_for(
            ROOT / serving / mod, "blocking-socket-call-in-timed-region"
        ) == []
