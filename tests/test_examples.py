"""Example-program integration tests — the reference's runnable-examples-as-
integration-tests strategy (SURVEY.md §4), automated."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import _free_port_block

REPO = Path(__file__).resolve().parent.parent


def _mpirun(n, prog, *prog_args, timeout=120, env=None):
    port = _free_port_block(4)
    return subprocess.run(
        [sys.executable, "-m", "mpi_tpu.launch.mpirun",
         "--port-base", str(port), "--timeout", "30",
         str(n), prog, *prog_args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env=env)


@pytest.mark.integration
class TestBounce:
    def test_two_rank_sweep_small(self):
        # Full-size sweep is the benchmark; tests run a reduced sweep via
        # env-free arg passthrough is not worth plumbing — run full but it
        # is only 10MB x 10 reps on loopback.
        res = _mpirun(2, "examples/bounce.py", "--json")
        assert res.returncode == 0, res.stderr
        # raw_decode from the first brace: immune to another child's
        # output landing on the same line (same interleaving class as
        # the helloworld flake).
        start = res.stdout.index('{')
        payload = json.JSONDecoder().raw_decode(res.stdout[start:])[0]
        assert payload["sizes"][-1] == 10 ** 7
        assert len(payload["bytes_us"]) == len(payload["sizes"])
        assert all(v > 0 for v in payload["bytes_us"][1:])
        # Echo integrity is checked inside the example (exit!=0 on corrupt).

    def test_odd_rank_count_rejected(self):
        res = _mpirun(1, "examples/bounce.py")
        assert res.returncode != 0
        assert "even number of ranks" in res.stderr + res.stdout


@pytest.mark.integration
class TestStencil:
    def test_host_jacobi_4_ranks(self):
        res = _mpirun(4, "examples/stencil.py")
        assert res.returncode == 0, res.stderr
        assert "host Jacobi ok: 4 ranks" in res.stdout
        # The example exits nonzero on any mismatch vs the dense
        # reference, so success == bitwise-verified halos.


@pytest.mark.integration
class TestOnesided:
    def test_tickets_and_board_4_ranks(self):
        res = _mpirun(4, "examples/onesided.py")
        assert res.returncode == 0, res.stderr
        # Each rank self-verifies (exit!=0 on mismatch); spot-check one.
        assert "rank 3: ticket 3, board [0, 11, 22, 33]" in res.stdout


@pytest.mark.integration
class TestCommGroups:
    def test_2x2_grid(self):
        res = _mpirun(4, "examples/comm_groups.py")
        assert res.returncode == 0, res.stderr
        assert "grid 2x2: per-column sums [2.0, 4.0] (total 6.0)" \
            in res.stdout
        # Every rank verifies its own row/col reductions (exit!=0 on
        # mismatch); spot-check one line of the per-rank report.
        assert "rank 3 = grid (1, 1)  row_sum=5.0  col_sum=4.0" \
            in res.stdout


@pytest.mark.integration
class TestServe:
    def test_serve_demo_all_paths_agree(self):
        # single process (no launcher): decode + int8 + speculative,
        # exiting nonzero if speculative output diverges from greedy.
        res = subprocess.run(
            [sys.executable, "examples/serve.py", "--devices", "1",
             "--tokens", "24", "--prompt-len", "16"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        # the divergence report lands on stdout; surface both streams
        assert res.returncode == 0, (res.stdout[-400:], res.stderr[-400:])
        assert "speculative == greedy: True" in res.stdout
        assert "int8 output valid: True" in res.stdout


@pytest.mark.integration
class TestMpi4pyPort:
    def test_unmodified_mpi4py_script_4_ranks(self):
        res = _mpirun(4, "examples/mpi4py_port.py")
        assert res.returncode == 0, res.stderr[-800:]
        out = res.stdout
        assert out.count("mpi4py surface OK") == 4
        assert "pi=3.141593" in out


@pytest.mark.integration
class TestXlaBackendInvocation:
    def test_documented_env_var_spelling_works(self):
        """`JAX_PLATFORMS=cpu python examples/helloworld.py
        --mpi-backend xla --mpi-ranks 8` — with NO XLA_FLAGS: run_main
        pins the platform via jax.config before the first device query
        and sizes the virtual cpu mesh from --mpi-ranks."""
        import os

        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run(
            [sys.executable, "examples/helloworld.py",
             "--mpi-backend", "xla", "--mpi-ranks", "8"],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env=env)
        assert res.returncode == 0, res.stderr[-800:]
        assert res.stdout.count("<- rank 7:") == 8


@pytest.mark.integration
class TestSsmExample:
    def test_ssm_example_runs(self):
        res = subprocess.run(
            [sys.executable, "examples/ssm.py", "--devices", "2",
             "--steps", "120"],
            capture_output=True, text=True, timeout=420, cwd=REPO)
        assert res.returncode == 0, res.stderr[-800:] + res.stdout[-400:]
        assert "ssm example OK" in res.stdout


@pytest.mark.integration
class TestDynamicProcessExamples:
    def test_spawn_master_worker(self):
        """examples/spawn.py: 2 parents spawn 3 workers at runtime;
        the parents' assertion verifies the gathered sum."""
        res = _mpirun(2, "examples/spawn.py", timeout=180)
        assert res.returncode == 0, res.stderr[-800:]
        assert "3 spawned workers summed" in res.stdout

    def test_client_server_rendezvous(self, tmp_path):
        """examples/client_server.py: an independent client world
        discovers the server's port through the name service and
        connects. The registry is pointed at a per-test dir — the
        example's fixed service name lives in a HOST-global registry
        by default, and two concurrent test runs on one machine would
        collide there (live-duplicate publish raises)."""
        import os

        env = {**os.environ, "MPI_TPU_NAMESERVER_DIR": str(tmp_path)}
        res = _mpirun(2, "examples/client_server.py", timeout=180,
                      env=env)
        assert res.returncode == 0, res.stderr[-800:]
        assert "accepted a 2-rank client world" in res.stdout
