"""Launcher-level chaos replays: the production train/serve loops under
injected faults, end to end through their real CLIs.

Covers the degradation paths the unit suite cannot reach in place:

* ``launch.train`` straggler mitigation (``--step-deadline``) — triggered
  deterministically by a ``step_stall`` injection — checkpoints + aborts
  with exit code 4;
* ``launch.train`` preemption (``preempt`` raises SIGTERM through the real
  ``PreemptionGuard``) — checkpoints + exits cleanly;
* ``launch.train`` non-finite guard: an isolated NaN step is skipped and
  training continues; ``--max-faults`` consecutive NaN steps
  checkpoint-before-abort with exit code 3;
* ``launch.serve`` replay: an all-failed run reports ``n/a`` percentiles
  (never NaN) and exits non-zero; a partial fault degrades only the
  poisoned requests and still exits 0 with the resilience summary printed.
"""
import pytest

from repro.checkpoint.manager import all_steps
from repro.launch import serve as launch_serve
from repro.launch import train as launch_train

_TRAIN_ARGS = ["--smoke", "--steps", "4", "--batch", "8", "--seq", "16",
               "--fault-backoff", "0.01"]
_SERVE_ARGS = ["--smoke", "--requests", "4", "--slots", "2", "--new", "4",
               "--prompt-len", "8", "--chunk", "4"]


def test_train_straggler_deadline_checkpoints_and_aborts(tmp_path, capsys):
    """A stalled step past --step-deadline aborts the run with a checkpoint
    (the fleet reschedules elsewhere) instead of hanging the job, and the
    process exits non-zero (code 4) so the abort is never read as success."""
    with pytest.raises(SystemExit) as exc:
        launch_train.main(_TRAIN_ARGS + [
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "100",
            "--step-deadline", "8", "--inject-faults", "step_stall@1:secs=10",
        ])
    assert exc.value.code == 4
    out = capsys.readouterr().out
    assert "exceeded deadline" in out
    assert all_steps(tmp_path) == [2]  # aborted at step 1: saved i+1
    assert "deadline -> checkpoint-abort" in out  # ResilienceLog summary


def test_train_preemption_guard_checkpoints_and_exits(tmp_path, capsys):
    """An injected SIGTERM goes through the real PreemptionGuard handler:
    the loop checkpoints at the end of the step and exits cleanly."""
    launch_train.main(_TRAIN_ARGS + [
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "100",
        "--inject-faults", "preempt@1",
    ])
    out = capsys.readouterr().out
    assert "preemption: saved, exiting" in out
    assert all_steps(tmp_path) == [2]
    assert "preempt -> checkpoint-exit" in out


def test_train_isolated_nan_step_is_skipped_and_run_completes(capsys):
    launch_train.main(_TRAIN_ARGS + ["--inject-faults", "nan_loss@1"])
    out = capsys.readouterr().out
    assert "update skipped (1/3 consecutive)" in out
    assert "done" in out  # the run recovered and finished
    assert "nonfinite -> skip-step x1" in out


def test_train_repeated_nan_checkpoint_before_abort(tmp_path, capsys):
    with pytest.raises(SystemExit) as exc:
        launch_train.main(_TRAIN_ARGS + [
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "100",
            "--inject-faults", "nan_loss@1:count=3", "--max-faults", "3",
        ])
    assert exc.value.code == 3
    out = capsys.readouterr().out
    assert "checkpointed, aborting" in out
    # checkpoint-before-abort: the last healthy params are on disk
    assert all_steps(tmp_path) == [4]
    assert "nonfinite -> checkpoint-abort" in out


def test_serve_all_failed_replay_reports_na_and_exits_nonzero(capsys):
    with pytest.raises(SystemExit) as exc:
        launch_serve.main(_SERVE_ARGS + [
            "--inject-faults", "nan_logits@0:count=999",
        ])
    assert exc.value.code == 2
    cap = capsys.readouterr()
    assert "e2e p50=n/a" in cap.out  # no NaN percentiles, ever
    assert "nan" not in cap.out.split("latency", 1)[1].split("\n", 1)[0]
    assert "error=4" in cap.out
    assert "no request finished cleanly" in cap.err


def test_serve_partial_fault_replay_degrades_and_exits_zero(capsys):
    assert launch_serve.main(_SERVE_ARGS + [
        "--inject-faults", "nan_logits@1:slot=0",
    ]) is None  # no SystemExit: healthy requests finished
    out = capsys.readouterr().out
    assert "error=" in out and "length=" in out  # mixed finish reasons
    assert "resilience:" in out and "retire-slot" in out
