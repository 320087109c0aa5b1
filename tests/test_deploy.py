"""Deploy-and-collect executor tests (2_final_multi_machine.sh analogue).

The real-cluster paths (ssh/rsync) are exercised as rendered dry-run
commands; execution is validated on the degenerate localhost cluster —
the same single-machine stand-in the reference uses (`mpirun
--oversubscribe`, SURVEY §4.4), but through the actual gRPC-coordinated
multi-process runtime.
"""

import socket
from pathlib import Path

from cuda_mpi_gpu_cluster_programming_tpu.parallel import deploy
from cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed import ClusterConfig


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_dry_run_renders_ssh_and_executes_nothing(tmp_path, capsys):
    cluster = ClusterConfig.parse(["myko@gpu-a sm_86", "myko@gpu-b sm_50"])
    results = deploy.deploy_and_collect(
        cluster,
        "cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed",
        workdir="/opt/work",
        log_root=str(tmp_path),
        dry_run=True,
    )
    out = capsys.readouterr().out
    assert "ssh myko@gpu-b" in out
    assert "JAX_PROCESS_ID=1" in out
    assert all(r.status == deploy.SKIPPED for r in results)
    assert not list(tmp_path.iterdir())  # nothing executed, no session dir


def test_reachability_local_and_dry_remote():
    cluster = ClusterConfig.parse(["localhost", "myko@far-host"])
    checks = deploy.check_reachable(cluster, dry_run=True)
    assert checks[0] == ("localhost", True, "local")
    host, ok, msg = checks[1]
    assert host == "far-host" and ok and msg.startswith("DRY: ssh")


def test_sync_code_local_copytree(tmp_path):
    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "a.py").write_text("x = 1\n")
    (src / "__pycache__").mkdir()
    (src / "__pycache__" / "junk.pyc").write_text("junk")
    dst = tmp_path / "dst"
    cluster = ClusterConfig.parse(["localhost"])
    actions = deploy.sync_code(cluster, str(src), str(dst))
    assert actions[0][1].startswith("copytree")
    assert (dst / "pkg" / "a.py").read_text() == "x = 1\n"
    assert not (dst / "__pycache__").exists()  # excluded


def test_sync_in_place_skips(tmp_path):
    cluster = ClusterConfig.parse(["localhost"])
    actions = deploy.sync_code(cluster, str(tmp_path), str(tmp_path))
    assert "in-place" in actions[0][1]


def test_parse_log():
    verdict, ms = deploy._parse_log(
        "pid=0: psum=10.0 expect=10.0 -> PASSED\n"
        "AlexNet TPU Forward Pass completed in 12.500 ms\n"
    )
    assert verdict == "PASSED" and ms == 12.5
    assert deploy._parse_log("no contract lines")[0] == ""


def test_localhost_cluster_end_to_end(tmp_path):
    """One command deploys a 2-host (degenerate: both local) inventory,
    collects per-host logs, and parses the self-verification verdicts."""
    cluster = ClusterConfig.parse(["localhost", "127.0.0.1"], port=_free_port())
    results = deploy.deploy_and_collect(
        cluster,
        "cuda_mpi_gpu_cluster_programming_tpu.parallel.distributed",
        workdir=str(Path(__file__).resolve().parent.parent),
        log_root=str(tmp_path),
        timeout_s=240.0,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    assert [r.status for r in results] == [deploy.OK, deploy.OK], [
        (r.status, r.tail) for r in results
    ]
    assert all(r.verdict == "PASSED" for r in results)
    for r in results:
        text = Path(r.log_file).read_text()
        assert "global_devices=4" in text  # 2 procs x 2 virtual devices
    session_dirs = list(tmp_path.iterdir())
    assert len(session_dirs) == 1
    summary = (session_dirs[0] / "summary.csv").read_text()
    assert summary.count("OK") == 2

    # the session CSV follows the analysis contract: it ingests like any
    # harness session (deploy.py docstring promise)
    from cuda_mpi_gpu_cluster_programming_tpu import analysis

    conn = analysis.connect(tmp_path / "w.sqlite")
    analysis.cmd_ingest(conn, tmp_path, None)
    rows = conn.execute(
        "SELECT variant, status FROM summary_runs ORDER BY rowid"
    ).fetchall()
    assert len(rows) == 2
    assert all(v == "MultiHost distributed" and s == "OK" for v, s in rows)
    conn.close()
