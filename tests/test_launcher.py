"""Launcher tests (reference: mpirun/gompirun/gompirun.go).

End-to-end: real OS processes wired by the flag ABI — the reference's
multi-node-without-a-cluster story on loopback."""

import subprocess
import sys
from pathlib import Path

import pytest

from mpi_tpu.launch import mpirun

from conftest import _free_port_block

REPO = Path(__file__).resolve().parent.parent


class TestBuildCommands:
    def test_flag_abi(self):
        # gompirun.go:68-90: each rank gets -mpi-addr :base+i and the full
        # -mpi-alladdr list, after the user's own args.
        cmds = mpirun.build_commands(3, "prog", ["--verbose"], port_base=6000)
        assert len(cmds) == 3
        for i, cmd in enumerate(cmds):
            assert cmd[0] == "prog"
            assert cmd[1] == "--verbose"
            assert cmd[cmd.index("--mpi-addr") + 1] == f":{6000 + i}"
            assert cmd[cmd.index("--mpi-alladdr") + 1] == ":6000,:6001,:6002"

    def test_py_prog_runs_under_python(self):
        cmds = mpirun.build_commands(1, "prog.py", [])
        assert cmds[0][:2] == [sys.executable, "prog.py"]

    def test_timeout_and_password_injection(self):
        cmds = mpirun.build_commands(2, "p", [], timeout=10.0, password="pw")
        cmd = cmds[0]
        assert cmd[cmd.index("--mpi-inittimeout") + 1] == "10s"
        assert cmd[cmd.index("--mpi-password") + 1] == "pw"

    def test_trace_stream_injection(self):
        cmds = mpirun.build_commands(2, "p", [], trace_stream="/tmp/spools")
        for cmd in cmds:
            assert cmd[cmd.index("--mpi-trace-stream") + 1] == "/tmp/spools"
        # Absent by default — the spool path must be opt-in.
        assert "--mpi-trace-stream" not in mpirun.build_commands(1, "p", [])[0]


def _run_cli(args, timeout=90):
    return subprocess.run(
        [sys.executable, "-m", "mpi_tpu.launch.mpirun", *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


@pytest.mark.integration
class TestEndToEnd:
    def test_helloworld_4_ranks(self):
        # BASELINE.json config 1: helloworld, 4 ranks, TCP backend, CPU only.
        port = _free_port_block(4)
        res = _run_cli(["--port-base", str(port), "--timeout", "30",
                        "4", "examples/helloworld.py"])
        assert res.returncode == 0, res.stderr
        # Count records, not lines: the four children share one pipe,
        # so two records can land on one line when a child's buffer
        # flushes mid-line (observed ~1-in-3 under load) — the
        # greetings are all present either way.
        assert res.stdout.count("<- rank") == 16  # 4 ranks x 4 greetings

    def test_child_failure_propagates_exit_code(self, tmp_path):
        prog = tmp_path / "boom.py"
        prog.write_text("import sys; sys.exit(3)\n")
        res = _run_cli(["2", str(prog)])
        assert res.returncode == 3
        assert "exited with code 3" in res.stderr

    def test_single_rank(self, tmp_path):
        prog = tmp_path / "solo.py"
        prog.write_text(
            "import sys; sys.path.insert(0, %r)\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "print('rank', mpi_tpu.rank(), 'size', mpi_tpu.size())\n"
            "mpi_tpu.finalize()\n" % str(REPO))
        port = _free_port_block(4)
        res = _run_cli(["--port-base", str(port), "1", str(prog)])
        assert res.returncode == 0, res.stderr
        assert "rank 0 size 1" in res.stdout

    def test_bad_usage(self):
        res = _run_cli(["0", "prog"])
        assert res.returncode == 2
