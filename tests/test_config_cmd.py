"""Config layering (env over yaml) and the two process entrypoints."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from ksim_tpu.config import load_config
from ksim_tpu.errors import InvalidConfigError
from tests.helpers import make_node, make_pod, sanitized_cpu_env

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def clean_env(monkeypatch):
    for k in (
        "PORT",
        "CORS_ALLOWED_ORIGIN_LIST",
        "KUBE_SCHEDULER_CONFIG_PATH",
        "EXTERNAL_IMPORT_ENABLED",
        "RESOURCE_SYNC_ENABLED",
        "EXTERNAL_SNAPSHOT_PATH",
        "KUBE_CONFIG",
        "KUBECONFIG",
    ):
        monkeypatch.delenv(k, raising=False)
    return monkeypatch


def test_yaml_plus_env_layering(tmp_path, clean_env):
    sched = tmp_path / "scheduler.yaml"
    sched.write_text("profiles:\n- schedulerName: my-sched\n")
    cfg_file = tmp_path / "config.yaml"
    cfg_file.write_text(
        "apiVersion: kube-scheduler-simulator-config/v1alpha1\n"
        "kind: SimulatorConfiguration\n"
        "port: 3131\n"
        "corsAllowedOriginList:\n- http://localhost:3000\n"
        f"kubeSchedulerConfigPath: {sched}\n"
        "etcdURL: http://ignored:2379\n"  # KWOK-topology field: ignored
    )
    cfg = load_config(str(cfg_file))
    assert cfg.port == 3131
    assert cfg.cors_allowed_origin_list == ("http://localhost:3000",)
    assert cfg.initial_scheduler_cfg["profiles"][0]["schedulerName"] == "my-sched"
    # Env overrides yaml (reference getPort: PORT first).
    clean_env.setenv("PORT", "4545")
    assert load_config(str(cfg_file)).port == 4545


def test_import_modes_mutually_exclusive(tmp_path, clean_env):
    cfg_file = tmp_path / "config.yaml"
    cfg_file.write_text(
        "port: 1212\nexternalImportEnabled: true\nresourceSyncEnabled: true\n"
        "externalSnapshotPath: /tmp/x.json\n"
    )
    with pytest.raises(InvalidConfigError):
        load_config(str(cfg_file))
    cfg_file.write_text("port: 1212\nexternalImportEnabled: true\n")
    with pytest.raises(InvalidConfigError):
        load_config(str(cfg_file))  # import without a source
    # kubeConfig is an alternative source (reference config.go:88-114)...
    cfg_file.write_text(
        "port: 1212\nresourceSyncEnabled: true\nkubeConfig: /tmp/kc.yaml\n"
    )
    assert load_config(str(cfg_file)).kube_config == "/tmp/kc.yaml"
    # The reference's KUBECONFIG env var works as a fallback source...
    clean_env.setenv("KUBECONFIG", "/tmp/ambient-kc.yaml")
    cfg_file.write_text("port: 1212\nresourceSyncEnabled: true\n")
    assert load_config(str(cfg_file)).kube_config == "/tmp/ambient-kc.yaml"
    # ...but never conflicts with an explicitly configured snapshot path.
    cfg_file.write_text(
        "port: 1212\nexternalImportEnabled: true\n"
        "externalSnapshotPath: /tmp/x.json\n"
    )
    cfg = load_config(str(cfg_file))
    assert cfg.external_snapshot_path == "/tmp/x.json" and not cfg.kube_config
    clean_env.delenv("KUBECONFIG")
    # ...but not alongside a snapshot file.
    cfg_file.write_text(
        "port: 1212\nexternalImportEnabled: true\nkubeConfig: /tmp/kc.yaml\n"
        "externalSnapshotPath: /tmp/x.json\n"
    )
    with pytest.raises(InvalidConfigError):
        load_config(str(cfg_file))


def _run_cmd(args, timeout=120):
    # CPU is plenty for entrypoint smoke tests; sanitized_cpu_env pins the
    # subprocess to the CPU backend so it never needs (or waits for) a chip.
    return subprocess.run(
        [sys.executable, "-m", *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=sanitized_cpu_env(),
    )


def test_scheduler_entrypoint_schedules_snapshot(tmp_path):
    snap = {
        "nodes": [make_node("n0", cpu="4", memory="8Gi")],
        "pods": [make_pod("p0", cpu="1", memory="1Gi")],
        "pvs": [], "pvcs": [], "storageClasses": [], "priorityClasses": [],
        "namespaces": [], "schedulerConfig": None,
    }
    snap_file = tmp_path / "snap.json"
    snap_file.write_text(json.dumps(snap))
    out_file = tmp_path / "out.json"
    proc = _run_cmd(
        ["ksim_tpu.cmd.scheduler", "--snapshot", str(snap_file), "--out", str(out_file)]
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(out_file.read_text())
    assert result["pods"][0]["spec"]["nodeName"] == "n0"
    anno = result["pods"][0]["metadata"]["annotations"]
    assert anno["kube-scheduler-simulator.sigs.k8s.io/selected-node"] == "n0"


def test_config_write_back(tmp_path):
    """Applying a config persists it to the configured scheduler.yaml
    (the reference's UpdateSchedulerConfig rewrite)."""
    import yaml

    from ksim_tpu.scheduler.service import SchedulerService
    from ksim_tpu.state.cluster import ClusterStore

    path = tmp_path / "scheduler.yaml"
    svc = SchedulerService(ClusterStore(), config_path=str(path))
    svc.apply_scheduler_config({"profiles": [{"schedulerName": "x"}]})
    assert yaml.safe_load(path.read_text())["profiles"] == [{"schedulerName": "x"}]


def test_compile_cache_is_placed_from_outside(tmp_path):
    """One knob places the persistent compile cache, and it is JAX's own:
    with JAX_COMPILATION_CACHE_DIR set no code path overrides it (a
    subprocess reads jax.config back); unset, the cache is the fixed
    <repo>/.jax_cache/<host> — read back from THIS process, whose
    conftest called enable_compilation_cache() the same way.  Never
    under the home directory, never a moving path."""
    import os

    import jax

    here = jax.config.jax_compilation_cache_dir
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert here == os.environ["JAX_COMPILATION_CACHE_DIR"]
    else:
        assert Path(here).parent == REPO / ".jax_cache"
        assert Path(here).name.startswith("host-")
    env = sanitized_cpu_env(
        {"HOME": str(tmp_path / "home"), "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "x")}
    )
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import jax; from ksim_tpu.util import enable_compilation_cache; "
            "enable_compilation_cache(); print(jax.config.jax_compilation_cache_dir, "
            "jax.config.jax_persistent_cache_min_compile_time_secs)",
        ],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(tmp_path / "x"), "0.5"]
    assert not (tmp_path / "home").exists()
