"""Backend fleet launcher: N serving processes behind one FleetRouter.

The CPU-testable stand-in ROADMAP item 3 names for one-frontend-per-host
over ``parallel/deploy.py``: each backend is a REAL OS process (module
entry ``python -m cuda_mpi_gpu_cluster_programming_tpu.serving.fleet
--child ...``) building its own :class:`~.server.InferenceServer` +
:class:`~.frontend.ServingFrontend` on an ephemeral port and announcing
readiness with one machine-parsed line::

    FLEET_READY name=b0 port=41231 platform=tpu ndev=1

**One process for each chip.** A TPU chip belongs to one process at a
time, so on a TPU host the launcher (a) refuses to start from a process
that has already initialised a JAX backend — that process holds every
chip and each child would hang opening one — (b) pins child ``i`` to
local chip ``i`` through libtpu's per-process environment
(:func:`chip_pin_env`), and (c) refuses ``n`` above the local chip count
up front. Off TPU (no chip device nodes, the CPU test mesh) children
inherit the parent's environment unchanged.

So host loss is a process fault, not a thread fault — the drills that
matter (``host_loss`` chaos SIGKILLs a backend mid-load) exercise a
kill(2) across a process boundary, the thing every earlier drill
(device loss, SDC, flap) could not: those all die *inside* one process.

Each backend writes its own journal (``<journal_dir>/backend_<i>.jsonl``)
beside the router's (``<journal_dir>/router.jsonl``);
``observability.export.load_records`` on the directory stitches all of
them into one Perfetto timeline.

The parent-side :class:`BackendFleet` spawns/kills/restarts children:
``kill(i)`` is SIGKILL (host loss — no goodbye), ``restart(i)`` respawns
the slot on a NEW ephemeral port (a replacement host) and returns the
url for :meth:`FleetRouter.replace_backend` — the slot's hash-ring
position never moves, and the restarted backend still re-admits through
the router's probation.

:func:`maybe_host_loss` is the chaos consumer: the seeded ``host_loss``
site picks its victim as ``seed % n`` — deterministic per spec, like
every other chaos site.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from ..resilience import chaos

READY_PREFIX = "FLEET_READY"
_PKG_ROOT = Path(__file__).resolve().parents[2]  # repo root (package parent)


class FleetError(RuntimeError):
    pass


_GOOGLE_PCI_VENDOR = "0x1ae0"


def local_tpu_chips(
    dev: str = "/dev", iommu_groups: str = "/sys/kernel/iommu_groups"
) -> int:
    """TPU chips this host hands to its processes, counted without opening
    one (the launcher must stay off JAX): the ``/dev/accelN`` nodes, or the
    ``/dev/vfio/N`` nodes whose IOMMU group N holds a Google PCI function.
    A VFIO group bound to any other device is not a chip, and a chip in
    sysfs whose group node is not exposed is not this host's to open (the
    one-chip v5e machine lists four and exposes one)."""
    accel = glob.glob(f"{dev}/accel[0-9]*")
    if accel:
        return len(accel)
    return sum(
        any(
            Path(vendor).read_text().strip() == _GOOGLE_PCI_VENDOR
            for vendor in glob.glob(
                f"{iommu_groups}/{Path(node).name}/devices/*/vendor"
            )
        )
        for node in glob.glob(f"{dev}/vfio/[0-9]*")
    )


def jax_backend_initialised() -> bool:
    """Whether this process has opened a JAX backend (and so holds the
    host's chips), asked without opening one. jax 0.9.0 has no public
    spelling; this is the one place the private one is named
    (tests/test_router.py checks it is still there)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def chip_pin_env(index: int) -> Dict[str, str]:
    """libtpu environment that confines one process to local chip
    ``index`` as a standalone one-chip slice (own mesh-controller and
    metrics ports, so sibling processes on the host do not collide)."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + index}",
        "TPU_MESH_CONTROLLER_PORT": str(8476 + index),
        "TPU_RUNTIME_METRICS_PORTS": str(8431 + index),
    }


class BackendProc:
    """One spawned backend: process handle + announced endpoint."""

    def __init__(
        self,
        index: int,
        proc: subprocess.Popen,
        ready: Dict[str, str],
        journal_path: str,
    ):
        self.index = index
        self.name = f"b{index}"
        self.proc = proc
        self.port = int(ready["port"])
        self.platform = ready.get("platform", "")
        self.n_devices = int(ready.get("ndev", 0))
        self.journal_path = journal_path

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


def _read_ready(proc: subprocess.Popen, timeout_s: float) -> Dict[str, str]:
    """Scan child stdout for the READY line and return its ``key=value``
    fields (bounded — a backend that never comes up is a spawn failure,
    not a hang; a child that exits closes its stdout and fails at once).
    The scan runs in a helper thread so a stuck child can't block the
    launcher past its deadline."""
    found: List[Dict[str, str]] = []
    err: List[str] = []

    def _scan() -> None:
        tail: List[str] = []
        for line in proc.stdout:  # type: ignore[union-attr]
            tail.append(line.rstrip()[-200:])
            if line.startswith(READY_PREFIX):
                fields = dict(
                    tok.split("=", 1) for tok in line.split()[1:] if "=" in tok
                )
                if "port" in fields:
                    found.append(fields)
                    return
        err.append("; ".join(tail[-5:]))

    t = threading.Thread(target=_scan, daemon=True)
    t.start()
    t.join(timeout_s)
    if not found:
        proc.kill()
        tail = err[0] if err else "no output"
        raise FleetError(
            f"backend never announced {READY_PREFIX} within {timeout_s}s "
            f"(rc={proc.poll()}, tail: {tail})"
        )
    return found[0]


class BackendFleet:
    """Spawn and manage N backend serving processes.

    ``journal_dir`` receives one ``backend_<i>.jsonl`` per backend (and
    is where callers point the router's own journal, so one directory
    exports as one stitched timeline). Children inherit the environment
    plus a PYTHONPATH entry for the repo root, so the fleet spawns
    correctly from any cwd; on a TPU host each is also pinned to its own
    chip (module docstring).
    """

    def __init__(
        self,
        n: int,
        journal_dir,
        *,
        height: int = 63,
        width: int = 63,
        max_batch: int = 4,
        config: str = "v1_jit",
        slo: bool = True,
        slo_scale: float = 1.0,
        spawn_timeout_s: float = 240.0,
        env: Optional[Dict[str, str]] = None,
        controller=None,
    ):
        if n < 1:
            raise ValueError("fleet needs n >= 1 backends")
        self.n = n
        self.journal_dir = Path(journal_dir)
        self.height, self.width = height, width
        self.max_batch, self.config, self.slo = max_batch, config, slo
        # Scales every class latency budget + deadline in the children
        # (SLOPolicy.scaled — the replay what-if dial, live): the fleet
        # pressure drill tightens SLOs so a CI-sized swell burns
        # measurably instead of hiding under second-scale budgets.
        self.slo_scale = slo_scale
        self.spawn_timeout_s = spawn_timeout_s
        self._extra_env = dict(env or {})
        # Optional ControllerConfig (or its to_obj dict): every child
        # runs an Autopilot — the fleet-control drills (ISSUE 20) need N
        # real controllers to arbitrate across.
        self.controller = controller
        self.backends: List[Optional[BackendProc]] = [None] * n
        # Pin one chip per child unless the children are held to the CPU.
        child_platforms = {**os.environ, **self._extra_env}.get(
            "JAX_PLATFORMS", ""
        )
        self._chips = (
            0 if child_platforms.split(",")[0] == "cpu" else local_tpu_chips()
        )

    def _spawn(self, index: int) -> BackendProc:
        jpath = str(self.journal_dir / f"backend_{index}.jsonl")
        cmd = [
            sys.executable, "-m",
            "cuda_mpi_gpu_cluster_programming_tpu.serving.fleet",
            "--child", "--name", f"b{index}",
            "--config", self.config,
            "--height", str(self.height), "--width", str(self.width),
            "--max-batch", str(self.max_batch),
            "--journal", jpath, "--port", "0",
        ]
        if self.slo:
            cmd.append("--slo")
            if self.slo_scale != 1.0:
                cmd.extend(["--slo-scale", repr(self.slo_scale)])
        if self.controller is not None:
            import json

            obj = (
                self.controller
                if isinstance(self.controller, dict)
                else self.controller.to_obj()
            )
            cmd.extend(["--controller", json.dumps(obj)])
        env = {**os.environ, **self._extra_env}
        env["PYTHONPATH"] = (
            str(_PKG_ROOT) + os.pathsep + env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep)
        # Chaos must not recurse into children: the parent owns the
        # host_loss budget; a child re-drawing it would double-fire.
        env.pop(chaos.CHAOS_ENV, None)
        if self._chips:
            env.update(chip_pin_env(index))
        proc = subprocess.Popen(
            cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        backend = BackendProc(
            index, proc, _read_ready(proc, self.spawn_timeout_s), jpath
        )
        if self._chips and (
            backend.platform != "tpu" or backend.n_devices != 1
        ):
            proc.kill()
            raise FleetError(
                f"backend b{index} was pinned to chip {index} but came up "
                f"on platform={backend.platform!r} with "
                f"{backend.n_devices} device(s)"
            )
        return backend

    def start(self) -> "BackendFleet":
        if self._chips:
            if self.n > self._chips:
                raise FleetError(
                    f"a fleet of {self.n} backends needs {self.n} local TPU "
                    f"chips (one process per chip), this host has "
                    f"{self._chips}"
                )
            if jax_backend_initialised():
                raise FleetError(
                    "this process has already initialised a JAX backend and "
                    "holds the host's TPU chips; a backend fleet must be "
                    "launched from a process that has not touched JAX"
                )
        self.journal_dir.mkdir(parents=True, exist_ok=True)
        for i in range(self.n):
            self.backends[i] = self._spawn(i)
        return self

    def urls(self) -> List[str]:
        out = []
        for b in self.backends:
            if b is None:
                raise FleetError("fleet not started")
            out.append(b.url)
        return out

    def kill(self, index: int) -> None:
        """SIGKILL — host loss, no drain, no goodbye. The router finds
        out the way production does: requests die and probes miss."""
        b = self.backends[index]
        if b is not None:
            b.proc.kill()
            b.proc.wait(10.0)

    def restart(self, index: int) -> str:
        """Respawn a dead slot on a new ephemeral port (a replacement
        host keeps the slot's ring position, not its address). Returns
        the new url for ``FleetRouter.replace_backend``."""
        old = self.backends[index]
        if old is not None and old.alive:
            raise FleetError(f"backend {index} still alive; kill it first")
        self.backends[index] = self._spawn(index)
        return self.backends[index].url

    def stop(self) -> None:
        for b in self.backends:
            if b is None or not b.alive:
                continue
            b.proc.terminate()
        for b in self.backends:
            if b is None:
                continue
            try:
                b.proc.wait(10.0)
            except subprocess.TimeoutExpired:
                b.proc.kill()
                b.proc.wait(10.0)

    def __enter__(self) -> "BackendFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def maybe_host_loss(fleet: BackendFleet) -> Optional[int]:
    """Fire the seeded ``host_loss`` chaos site if armed: SIGKILL one
    backend chosen as ``seed % n`` (deterministic per CHAOS_SPEC, the
    same discipline as every other site). Returns the killed index, or
    None when the site didn't fire."""
    ch = chaos.active()
    if ch is None or not ch.draw("host_loss"):
        return None
    idx = ch.spec.seed % fleet.n
    fleet.kill(idx)
    return idx


# ------------------------------------------------------------ child entry


def _child_main(argv: List[str]) -> int:
    """One backend process: InferenceServer + ServingFrontend on an
    ephemeral port, READY line on stdout, then park until killed."""
    import argparse
    import dataclasses

    ap = argparse.ArgumentParser(prog="serving.fleet --child")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--name", default="b0")
    ap.add_argument("--config", default="v1_jit")
    ap.add_argument("--height", type=int, default=63)
    ap.add_argument("--width", type=int, default=63)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--journal", default="")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--slo", action="store_true")
    ap.add_argument("--slo-scale", type=float, default=1.0)
    ap.add_argument("--controller", default="")
    args = ap.parse_args(argv)

    from ..models.alexnet import BLOCKS12
    from .frontend import ServingFrontend
    from .server import InferenceServer, ServeConfig

    model_cfg = dataclasses.replace(
        BLOCKS12, in_height=args.height, in_width=args.width
    )
    slo = None
    if args.slo:
        from .batcher import power_of_two_buckets
        from .traffic import default_class_mix, slo_policy

        slo = slo_policy(
            default_class_mix(power_of_two_buckets(args.max_batch))
        )
        if args.slo_scale != 1.0:
            slo = slo.scaled(args.slo_scale)
    controller = None
    if args.controller:
        import json

        controller = json.loads(args.controller)
    srv = InferenceServer(
        ServeConfig(
            config=args.config,
            max_batch=args.max_batch,
            model_cfg=model_cfg,
            journal_path=args.journal or None,
            slo=slo,
            controller=controller,
        )
    )
    srv.start()
    fe = ServingFrontend(srv, port=args.port).start()
    import jax

    print(
        f"{READY_PREFIX} name={args.name} port={fe.port} "
        f"platform={jax.default_backend()} ndev={jax.local_device_count()}",
        flush=True,
    )
    try:
        while True:  # host loss is SIGKILL; orderly stop is SIGTERM
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        fe.stop()
        srv.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(_child_main(sys.argv[1:]))
