"""Launcher unit tests (reference analog: ``test/single/test_run.py`` —
host parsing, assignment math, CLI parsing with mocked exec)."""

import os
import textwrap

import pytest

from horovod_tpu.runner import hosts as hosts_mod
from horovod_tpu.runner import launch as launch_mod


def test_parse_hosts():
    hs = hosts_mod.parse_hosts("a:4,b,c:2")
    assert [(h.hostname, h.slots) for h in hs] == [("a", 4), ("b", 1), ("c", 2)]


def test_parse_host_files(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text(textwrap.dedent("""\
        # comment
        node1 slots=4
        node2 slots=2
        node3
    """))
    hs = hosts_mod.parse_host_files(str(f))
    assert [(h.hostname, h.slots) for h in hs] == [
        ("node1", 4), ("node2", 2), ("node3", 1)
    ]


def test_get_host_assignments():
    hs = hosts_mod.parse_hosts("a:2,b:2")
    slots = hosts_mod.get_host_assignments(hs, 4)
    assert [(s.hostname, s.rank, s.local_rank, s.cross_rank) for s in slots] == [
        ("a", 0, 0, 0), ("a", 1, 1, 0), ("b", 2, 0, 1), ("b", 3, 1, 1)
    ]
    assert all(s.size == 4 and s.cross_size == 2 for s in slots)
    assert slots[0].local_size == 2


def test_get_host_assignments_partial_last_host():
    hs = hosts_mod.parse_hosts("a:2,b:4")
    slots = hosts_mod.get_host_assignments(hs, 3)
    assert len(slots) == 3
    assert slots[2].hostname == "b" and slots[2].local_size == 1


def test_get_host_assignments_insufficient():
    with pytest.raises(ValueError, match="only 2 slot"):
        hosts_mod.get_host_assignments(hosts_mod.parse_hosts("a:2"), 4)


def test_parse_args_basic():
    args = launch_mod.parse_args(["-np", "4", "python", "train.py", "--lr", "1"])
    assert args.np == 4
    assert args.command == ["python", "train.py", "--lr", "1"]


def test_parse_args_knobs_to_env():
    args = launch_mod.parse_args([
        "-np", "2", "--fusion-threshold-mb", "32", "--timeline-filename",
        "/tmp/tl.json", "--autotune", "--log-level", "debug", "python", "x.py",
    ])
    env = launch_mod.env_from_args(args)
    assert env["HVD_TPU_FUSION_THRESHOLD"] == str(32 << 20)
    assert env["HVD_TPU_TIMELINE"] == "/tmp/tl.json"
    assert env["HVD_TPU_AUTOTUNE"] == "1"
    assert env["HVD_TPU_LOG_LEVEL"] == "debug"


def test_parse_args_requires_np_and_command():
    with pytest.raises(SystemExit):
        launch_mod.parse_args(["python", "x.py"])
    with pytest.raises(SystemExit):
        launch_mod.parse_args(["-np", "2"])


def test_py_controller_roundtrip():
    from horovod_tpu.runner import controller_py as cp

    srv = cp.PyControllerServer(secret="s3cret", world=2)
    try:
        c1 = cp.PyControllerClient("127.0.0.1", srv.port, "s3cret", 0)
        c2 = cp.PyControllerClient("127.0.0.1", srv.port, "s3cret", 1)
        c1.put("sc", "k", b"\x00binary\xff")
        assert c2.get("sc", "k", timeout_ms=1000) == b"\x00binary\xff"
        assert c2.get("sc", "nope", timeout_ms=50) is None
        import threading

        ok = [False, False]
        ts = [
            threading.Thread(
                target=lambda i=i, c=c: ok.__setitem__(
                    i, c.barrier("b0", 2, timeout_ms=3000)
                ),
            )
            for i, c in enumerate((c1, c2))
        ]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert all(ok)
        # auth failure
        evil = cp.PyControllerClient("127.0.0.1", srv.port, "wrong", 2)
        with pytest.raises(OSError):
            evil.put("sc", "k2", b"x")
        evil.close()
        c1.close()
        c2.close()
    finally:
        srv.stop()


def test_native_python_controller_interop():
    """The Python client must speak the native server's protocol and
    vice versa (same wire format + HMAC)."""
    from horovod_tpu import native
    from horovod_tpu.runner import controller_py as cp

    if not native.available():
        pytest.skip("native core not built")
    # native server <- python client
    srv = native.ControllerServer(secret="tok", world=1)
    try:
        pyc = cp.PyControllerClient("127.0.0.1", srv.port, "tok", 0)
        pyc.put("s", "k", b"value1")
        assert pyc.get("s", "k", timeout_ms=1000) == b"value1"
        pyc.close()
    finally:
        srv.stop()
    # python server <- native client
    pysrv = cp.PyControllerServer(secret="tok2", world=1)
    try:
        nc = native.ControllerClient("127.0.0.1", pysrv.port, "tok2", 0)
        nc.put("s", "k", b"value2")
        assert nc.get("s", "k", timeout_ms=1000) == b"value2"
        assert nc.barrier("bb", 1, timeout_ms=1000)
        nc.close()
    finally:
        pysrv.stop()


# ---- config file + check-build (reference launch.py:110, config_parser) ----

def test_config_parser_simple_yaml(tmp_path):
    from horovod_tpu.runner.config_parser import parse_config_file

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "params:\n"
        "  fusion_threshold_mb: 128   # comment\n"
        "timeline:\n"
        "  filename: /tmp/tl.json\n"
        "autotune:\n"
        "  enabled: true\n"
        "elastic:\n"
        "  min_np: 2\n"
    )
    parsed = parse_config_file(str(cfg))
    assert parsed["params"]["fusion_threshold_mb"] == 128
    assert parsed["timeline"]["filename"] == "/tmp/tl.json"
    assert parsed["autotune"]["enabled"] is True
    assert parsed["elastic"]["min_np"] == 2


def test_config_parser_json(tmp_path):
    from horovod_tpu.runner.config_parser import parse_config_file

    cfg = tmp_path / "cfg.json"
    cfg.write_text('{"params": {"fusion_threshold_mb": 64}}')
    assert parse_config_file(str(cfg))["params"]["fusion_threshold_mb"] == 64


def test_config_file_feeds_args_cli_wins(tmp_path):
    from horovod_tpu.runner.launch import env_from_args, parse_args

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "params:\n  fusion_threshold_mb: 128\nlogging:\n  level: debug\n"
    )
    args = parse_args([
        "-np", "2", "--config-file", str(cfg),
        "--fusion-threshold-mb", "32",  # CLI beats config
        "python", "train.py",
    ])
    env = env_from_args(args)
    assert env["HVD_TPU_FUSION_THRESHOLD"] == str(32 << 20)
    assert env["HVD_TPU_LOG_LEVEL"] == "debug"


def test_check_build_reports(capsys):
    from horovod_tpu.runner.launch import check_build

    check_build()
    out = capsys.readouterr().out
    assert "Available Frameworks" in out
    assert "[X] JAX" in out
    assert "native core" in out
    assert "Adasum" in out


def test_tpu_backend_configured_without_opening_a_backend(monkeypatch):
    """--check-build's TPU line: libtpu installed and JAX_PLATFORMS not
    ruling the TPU out — decided without calling jax.devices()."""
    import importlib.util

    from horovod_tpu.runner import launch

    has_libtpu = importlib.util.find_spec("libtpu") is not None
    assert launch.tpu_backend_configured({}) is has_libtpu
    assert launch.tpu_backend_configured(
        {"JAX_PLATFORMS": "tpu,cpu"}) is has_libtpu
    assert launch.tpu_backend_configured({"JAX_PLATFORMS": "cpu"}) is False
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    assert launch.tpu_backend_configured({}) is False


def test_one_tpu_process_per_host(monkeypatch):
    """Several TPU workers on one host are refused with an error that
    says to use one process per host (on the four-chip host they hung:
    PERF.md, Bring-up); CPU jobs and one-per-host TPU jobs pass."""
    from horovod_tpu import runner
    from horovod_tpu.runner import launch

    four_local = hosts_mod.get_host_assignments(
        [hosts_mod.HostInfo("localhost", 4)], 4)
    one_per_host = hosts_mod.get_host_assignments(
        [hosts_mod.HostInfo("a", 1), hosts_mod.HostInfo("b", 1)], 2)

    monkeypatch.setattr(launch, "tpu_backend_configured", lambda env: True)
    with pytest.raises(ValueError, match="one process per host"):
        launch.require_one_tpu_process_per_host(four_local)
    launch.require_one_tpu_process_per_host(one_per_host)
    # the CLI refuses before it starts anything, with a usage error
    assert launch.run_commandline(["-np", "4", "true"]) == 2
    with pytest.raises(ValueError, match="one process per host"):
        runner.run(lambda: 0, np=2)

    monkeypatch.setattr(launch, "tpu_backend_configured", lambda env: False)
    launch.require_one_tpu_process_per_host(four_local)


def test_run_refused_under_a_parent_that_holds_the_tpu(monkeypatch):
    from horovod_tpu import runner

    assert not runner._holds_tpu()  # this process runs on the CPU
    monkeypatch.setattr(runner, "_holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="already holds"):
        runner.run(lambda: 0, np=1)


def test_config_parser_hash_in_value(tmp_path):
    from horovod_tpu.runner.config_parser import parse_config_file

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "timeline:\n"
        "  filename: /data/run#3/tl.json\n"
        "params:\n"
        "  fusion_threshold_mb: 16  # trailing comment\n"
    )
    parsed = parse_config_file(str(cfg))
    assert parsed["timeline"]["filename"] == "/data/run#3/tl.json"
    assert parsed["params"]["fusion_threshold_mb"] == 16


def test_config_parser_apostrophe_in_value(tmp_path):
    from horovod_tpu.runner.config_parser import parse_config_file

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "timeline:\n"
        "  filename: user's tl.json  # note\n"
        "  quoted: '#literal'\n"
    )
    parsed = parse_config_file(str(cfg))
    assert parsed["timeline"]["filename"] == "user's tl.json"
    assert parsed["timeline"]["quoted"] == "#literal"


def test_compile_cache_helper(monkeypatch):
    """utils/compile_cache: with JAX_COMPILATION_CACHE_DIR set, code
    sets no directory at all; unset, the cache is the fixed
    <checkout>/.jax_cache — the same on every call (the path is part of
    the cache key) and never under a temporary directory."""
    import tempfile

    import jax

    from horovod_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert compile_cache.enable() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = compile_cache.enable(), compile_cache.enable()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert not first.startswith(tempfile.gettempdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_elastic_driver_defaults_compilation_cache(monkeypatch):
    """_with_compilation_cache: the helper's directory by default,
    explicit dir wins, driver-env dir is copied for remote workers,
    opt-out respected."""
    from horovod_tpu.runner.elastic_driver import _with_compilation_cache
    from horovod_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("HVD_TPU_NO_COMPILATION_CACHE", raising=False)

    env = _with_compilation_cache({})
    assert env["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR

    # explicit user dir wins
    env = _with_compilation_cache({"JAX_COMPILATION_CACHE_DIR": "/x"})
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/x"

    # driver-env dir is COPIED into the worker env (remote ssh workers
    # never inherit the driver environment), not merely skipped
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/driver/cache")
    env = _with_compilation_cache({})
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/driver/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")

    # opt-out respected
    monkeypatch.setenv("HVD_TPU_NO_COMPILATION_CACHE", "1")
    assert "JAX_COMPILATION_CACHE_DIR" not in _with_compilation_cache({})


def test_elastic_timeout_env_knob(monkeypatch):
    """HVD_TPU_ELASTIC_TIMEOUT / HOROVOD_ELASTIC_TIMEOUT set the
    wait_for_available_slots deadline (reference ELASTIC_TIMEOUT_SECS)."""
    from horovod_tpu.runner.elastic_driver import ElasticDriver

    class NoSlots:
        def available_slots(self):
            return 0

        current_hosts = {}

    drv = ElasticDriver.__new__(ElasticDriver)
    drv.host_manager = NoSlots()
    import threading

    drv._shutdown = threading.Event()
    monkeypatch.setenv("HVD_TPU_ELASTIC_TIMEOUT", "0")
    import time

    t0 = time.monotonic()
    assert not drv.wait_for_available_slots(2)
    assert time.monotonic() - t0 < 2.0  # returned at the env deadline

    # fractional timeouts parse (get_float, not get_int)
    monkeypatch.setenv("HVD_TPU_ELASTIC_TIMEOUT", "0.5")
    t0 = time.monotonic()
    assert not drv.wait_for_available_slots(2)
    assert time.monotonic() - t0 < 3.0

    # zero timeout still succeeds when capacity is already there
    class HasSlots:
        def available_slots(self):
            return 4

        current_hosts = {}

    drv.host_manager = HasSlots()
    monkeypatch.setenv("HVD_TPU_ELASTIC_TIMEOUT", "0")
    assert drv.wait_for_available_slots(2)


class TestNicProbe:
    """Mutual-interface probe (reference driver_service _run_probe /
    task_service.py:383 recast, VERDICT r3 missing-7)."""

    def test_all_local_is_loopback(self):
        from horovod_tpu.runner import exec_utils

        assert exec_utils.probe_routable_addr(["localhost"]) == "127.0.0.1"

    def test_picks_mutually_reachable_candidate(self, monkeypatch):
        from horovod_tpu.runner import exec_utils

        monkeypatch.setattr(
            exec_utils, "_local_candidate_addrs",
            lambda remotes: ["10.0.0.5", "192.168.1.5"],
        )
        # hostA can only route the 192 interface; hostB routes both
        results = {"hostA": {"192.168.1.5"},
                   "hostB": {"10.0.0.5", "192.168.1.5"}}
        addr = exec_utils.probe_routable_addr(
            ["hostA", "hostB"], _dial=lambda h: results[h]
        )
        assert addr == "192.168.1.5"

    def test_falls_back_with_warning_when_no_common(self, monkeypatch):
        from horovod_tpu.runner import exec_utils
        from horovod_tpu.utils.logging import get_logger

        monkeypatch.setattr(
            exec_utils, "_local_candidate_addrs",
            lambda remotes: ["10.0.0.5"],
        )
        warned = []
        monkeypatch.setattr(
            get_logger(), "warning",
            lambda msg, *a, **k: warned.append(msg % a if a else msg),
        )
        heuristic = exec_utils.routable_addr(["hostA"])
        addr = exec_utils.probe_routable_addr(
            ["hostA"], _dial=lambda h: set()
        )
        assert addr == heuristic
        assert any("NIC probe" in m for m in warned), warned

    def test_echo_listener_end_to_end(self, monkeypatch):
        """A dialer that REALLY dials the probe's listener from this
        machine: the token echo handshake must validate the address."""
        import re
        import socket as _socket

        from horovod_tpu.runner import exec_utils

        monkeypatch.setattr(
            exec_utils, "_local_candidate_addrs",
            lambda remotes: ["127.0.0.1"],  # dial loopback for the test
        )
        seen = {}

        def real_dial(host):
            # grab the port/token from the enclosing probe via its
            # listener: emulate the remote script faithfully
            srv_port = seen["port"]
            token = seen["token"]
            ok = set()
            try:
                s = _socket.create_connection(("127.0.0.1", srv_port),
                                              timeout=3)
                s.sendall(token.encode() + b"\n")
                if s.recv(64).strip() == token.encode():
                    ok.add("127.0.0.1")
                s.close()
            except OSError:
                pass
            return ok

        orig_ssh_dial = exec_utils._ssh_dial

        # intercept the internals to learn port+token, then delegate to
        # the real local dial
        real_probe = exec_utils.probe_routable_addr

        def spy_dial_factory(h, addrs, port, token, *a):
            seen["port"] = port
            seen["token"] = token
            return real_dial(h)

        monkeypatch.setattr(exec_utils, "_ssh_dial", spy_dial_factory)
        addr = real_probe(["some-remote-host"])
        assert addr == "127.0.0.1"

    def test_disable_knob(self, monkeypatch):
        from horovod_tpu.runner import exec_utils

        monkeypatch.setenv("HVD_TPU_NIC_PROBE", "0")
        called = []
        monkeypatch.setattr(
            exec_utils, "_local_candidate_addrs",
            lambda remotes: called.append(1) or [],
        )
        exec_utils.probe_routable_addr(["hostX"])
        assert not called  # probe skipped entirely
