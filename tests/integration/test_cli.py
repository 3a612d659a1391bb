"""CLI surface: info / run (incl. budget-calibrated DP) / serve (incl. secure mode).

The reference's CLI entry point dangles (``pyproject.toml:22-23`` names a module that
does not exist); these tests pin that ours actually drives the stack end-to-end.
"""

import asyncio
import json
import threading

import jax
import numpy as np
import pytest

from nanofed_tpu.cli import main


def test_info(capsys):
    assert main(["info"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert "mnist_cnn" in payload["models"]
    assert payload["devices"]


def test_compiling_commands_enable_the_compile_cache(tmp_path, capsys, monkeypatch):
    """run/loadtest keep their programs in the one persistent cache; info, which
    compiles nothing, does not touch the setting."""
    from nanofed_tpu import cli
    from nanofed_tpu.utils import platform

    calls = []
    monkeypatch.setattr(
        platform, "enable_compilation_cache", lambda: calls.append(1) or "unused"
    )
    assert main(["info"]) == 0
    assert calls == []
    monkeypatch.setattr(cli, "_cmd_run", lambda args: 0)
    assert main(["run"]) == 0
    assert calls == [1]
    capsys.readouterr()


def test_run_with_calibrated_dp(tmp_path, capsys):
    rc = main([
        "run", "--model", "digits_mlp", "--clients", "8", "--rounds", "2",
        "--epochs", "1", "--batch-size", "16", "--lr", "0.3",
        "--out-dir", str(tmp_path), "--dp-epsilon", "4.0",
    ])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["rounds_completed"] == 2
    # Budget calibration: the spend must land within the requested epsilon.
    assert 0 < summary["privacy_spent"]["epsilon_spent"] <= 4.0 + 1e-6


def test_serve_secure_round(capsys):
    """`nanofed-tpu serve --secure` hosts a masked round that real clients complete."""
    pytest.importorskip("cryptography")
    from nanofed_tpu.communication import HTTPClient
    from nanofed_tpu.models import get_model
    from nanofed_tpu.security.secure_agg import (
        ClientKeyPair,
        SecureAggregationConfig,
        mask_update,
    )

    import socket

    with socket.socket() as sock:  # free port: parallel/leaked runs can't collide
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    model = get_model("digits_mlp")  # default hidden must match serve's
    init = model.init(jax.random.key(0))
    cfg = SecureAggregationConfig(min_clients=3)
    rc_holder = {}

    def run_server():
        rc_holder["rc"] = main([
            "serve", "--model", "digits_mlp", "--port", str(port), "--rounds", "1",
            "--min-clients", "3", "--timeout", "30", "--secure",
        ])

    async def run_client(cid):
        kp = ClientKeyPair.generate()
        async with HTTPClient(f"http://127.0.0.1:{port}", cid, timeout_s=30) as c:
            for _ in range(200):
                try:
                    if await c.register_secagg(kp.public_bytes(), 10.0):
                        break
                except OSError:
                    pass  # server thread still binding the port
                await asyncio.sleep(0.05)
            roster = await c.fetch_secagg_roster()
            params = None
            for _ in range(200):
                try:
                    params, rnd, active = await c.fetch_global_model(like=init)
                    break
                except Exception:
                    await asyncio.sleep(0.05)
            assert params is not None
            masked = mask_update(
                model.init(jax.random.key(3)), roster.index_of(cid), kp,
                roster.ordered_keys(), rnd, cfg, weight=roster.weights[cid],
            )
            assert await c.submit_masked_update(masked, {})

    async def clients():
        await asyncio.gather(*(run_client(f"c{i}") for i in range(3)))

    # serve's default digits_mlp init must match the clients' template shapes.
    server_thread = threading.Thread(target=run_server, daemon=True)
    server_thread.start()
    asyncio.run(clients())
    server_thread.join(timeout=60)
    assert not server_thread.is_alive()
    assert rc_holder["rc"] == 0
    history = json.loads(capsys.readouterr().out)
    assert history[0]["status"] == "COMPLETED" and history[0]["secure"] is True


def test_serve_async_buffer_round(capsys):
    """`serve --async-buffer K` hosts FedBuff aggregations that real clients feed
    with no cohort barrier."""
    import socket

    from nanofed_tpu.communication import HTTPClient
    from nanofed_tpu.models import get_model

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    model = get_model("digits_mlp")
    init = model.init(jax.random.key(0))
    rc_holder = {}

    def run_server():
        rc_holder["rc"] = main([
            "serve", "--model", "digits_mlp", "--port", str(port), "--rounds", "3",
            "--timeout", "30", "--async-buffer", "2", "--staleness-window", "4",
        ])

    async def run_client(cid, seed):
        async with HTTPClient(f"http://127.0.0.1:{port}", cid, timeout_s=30) as c:
            params = None
            for _ in range(200):
                try:
                    params, rnd, active = await c.fetch_global_model(like=init)
                    break
                except Exception:
                    await asyncio.sleep(0.05)
            assert params is not None
            while True:
                try:
                    params, rnd, active = await c.fetch_global_model(like=init)
                    if not active:
                        return
                    fake = jax.tree.map(
                        lambda p, s=seed: p + 0.01 * (s + 1) * np.ones_like(p),
                        params,
                    )
                    await c.submit_update(fake, {"loss": 0.5, "num_samples": 10.0})
                except Exception:
                    return  # server already tore the socket down after the run
                await asyncio.sleep(0.01)

    async def clients():
        await asyncio.gather(*(run_client(f"c{i}", i) for i in range(3)))

    server_thread = threading.Thread(target=run_server, daemon=True)
    server_thread.start()
    asyncio.run(clients())
    server_thread.join(timeout=60)
    assert not server_thread.is_alive()
    assert rc_holder["rc"] == 0
    history = json.loads(capsys.readouterr().out)
    completed = [h for h in history if h["status"] == "COMPLETED"]
    assert len(completed) == 3
    assert all(h["num_clients"] == 2 for h in completed)  # exactly K per step


def test_serve_async_refuses_secure(capsys):
    rc = main(["serve", "--async-buffer", "2", "--secure"])
    assert rc == 2
    assert "--async-buffer" in capsys.readouterr().err


def test_serve_async_refuses_sync_only_cohort_flags(capsys):
    """Satellite regression: the sync-only cohort flags (--min-clients,
    --completion-rate, --max-clients) error when explicitly combined with
    --async-buffer, matching the --staleness-window refusal — FedBuff has no
    cohort barrier, so nothing would read them."""
    rc = main(["serve", "--async-buffer", "2", "--min-clients", "3"])
    assert rc == 2
    assert "--min-clients" in capsys.readouterr().err
    rc = main(["serve", "--async-buffer", "2", "--completion-rate", "0.5"])
    assert rc == 2
    assert "--completion-rate" in capsys.readouterr().err
    rc = main(["serve", "--async-buffer", "2", "--max-clients", "5"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--max-clients" in err and "async" in err
    rc = main(["serve", "--async-buffer", "2",
               "--min-clients", "3", "--completion-rate", "0.5"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--min-clients" in err and "--completion-rate" in err


def test_serve_async_flag_validation(capsys):
    """Mode-scoped flags fail fast instead of being silently ignored or escaping
    as coordinator tracebacks."""
    rc = main(["serve", "--staleness-window", "8"])
    assert rc == 2
    assert "--async-buffer" in capsys.readouterr().err
    rc = main(["serve", "--async-buffer", "0"])
    assert rc == 2
    assert "must be >= 1" in capsys.readouterr().err
    rc = main(["serve", "--async-buffer", "2", "--staleness-window", "0"])
    assert rc == 2
    assert "staleness-window" in capsys.readouterr().err


def test_metrics_summary_subcommand(tmp_path, capsys):
    """`nanofed-tpu metrics-summary` digests a run's telemetry.jsonl; a tree with
    none exits 1 with a pointer at --telemetry-dir."""
    import json as _json

    from nanofed_tpu.observability import MetricsRegistry, RunTelemetry

    tel = RunTelemetry(tmp_path / "run1", registry=MetricsRegistry())
    with tel.span("round", round=0):
        pass
    tel.record("round", round=0, status="COMPLETED", duration_s=0.125)
    tel.close()
    assert main(["metrics-summary", str(tmp_path)]) == 0
    summary = _json.loads(capsys.readouterr().out)
    assert summary["rounds"] == {"COMPLETED": 1}
    assert summary["phases"]["round"]["count"] == 1

    assert main(["metrics-summary", str(tmp_path / "empty")]) == 1
    assert "--telemetry-dir" in capsys.readouterr().err


def test_profile_subcommand_compiles_without_running(tmp_path, capsys):
    """`nanofed-tpu profile` compiles single-step, fused-block, and SCAFFOLD
    round programs on CPU WITHOUT running a federation, and the reports reach
    stdout + telemetry with compiler FLOPs, peak bytes, intensity, verdict."""
    rc = main([
        "profile", "--model", "digits_mlp", "--clients", "8",
        "--batch-size", "16", "--rounds-per-block", "2", "--json",
        "--telemetry-dir", str(tmp_path),
    ])
    assert rc == 0
    reports = json.loads(capsys.readouterr().out)
    assert {r["program"] for r in reports} == {
        "round_step", "round_block", "scaffold_round_step"
    }
    for r in reports:
        assert r["flops"] > 0
        assert r["peak_bytes"] > 0
        assert r["arithmetic_intensity"] > 0
        assert r["verdict"] == "no peak basis"  # CPU: no fabricated roofline
    (block,) = [r for r in reports if r["program"] == "round_block"]
    assert block["rounds"] == 2

    # Telemetry carries program_profile records — and NO round records: the
    # whole point is that nothing federated ran.
    telemetry = (tmp_path / "telemetry.jsonl").read_text()
    assert '"type": "program_profile"' in telemetry
    assert '"type": "round"' not in telemetry
    # metrics-summary digests them.
    assert main(["metrics-summary", str(tmp_path)]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert set(summary["program_profiles"]) == {
        "round_step", "round_block", "scaffold_round_step"
    }


def test_profile_table_output(capsys):
    rc = main([
        "profile", "--model", "digits_mlp", "--clients", "8",
        "--batch-size", "16", "--rounds-per-block", "1", "--no-scaffold",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "round_step" in out
    assert "roofline basis" in out
    assert "flops/round" in out


def test_run_robust_with_dp_fails_fast(capsys):
    rc = main(["run", "--robust-trim", "1", "--dp-epsilon", "4.0"])
    assert rc == 2
    assert "different sensitivity" in capsys.readouterr().err


def test_serve_flag_combinations_fail_fast(capsys):
    """Misconfigurations exit 2 with a pointed message BEFORE binding anything:
    --max-clients without the tolerant window (it would be silently ignored),
    and a cap below the minimum (the implicit freeze would close enrollment at a
    size the coordinator then waits on forever)."""
    rc = main(["serve", "--secure", "--min-clients", "3", "--max-clients", "10"])
    assert rc == 2
    assert "--dropout-tolerant" in capsys.readouterr().err
    rc = main(["serve", "--secure", "--dropout-tolerant",
               "--min-clients", "5", "--max-clients", "3"])
    assert rc == 2
    assert "must be >=" in capsys.readouterr().err


def test_chaos_plan_generates_host_and_client_faults(tmp_path, capsys):
    # stdout form: a valid, seeded plan with the requested host fault.
    rc = main(["chaos-plan", "--seed", "9", "--hosts", "3",
               "--host-crashes", "1", "--rounds", "6"])
    assert rc == 0
    plan = json.loads(capsys.readouterr().out)
    assert plan["seed"] == 9
    assert [e["kind"] for e in plan["events"]] == ["host_crash"]
    assert "host" in plan["events"][0]

    # file form round-trips through the loader serve/hostchaos use.
    from nanofed_tpu.faults import FaultPlan

    out = tmp_path / "plan.json"
    rc = main(["chaos-plan", "--seed", "1", "--clients", "8",
               "--crash-fraction", "0.25", "--hosts", "2",
               "--host-stalls", "1", "--out", str(out)])
    assert rc == 0
    loaded = FaultPlan.load(out)
    kinds = sorted(e.kind for e in loaded.events)
    assert kinds == ["crash", "crash", "host_stall"]

    # misconfiguration and empty plans are refusals, not silent successes.
    assert main(["chaos-plan", "--host-crashes", "1"]) == 2
    assert main(["chaos-plan", "--clients", "8"]) == 2
