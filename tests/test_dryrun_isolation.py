"""The dryrun stage runner must isolate failures (round-3 postmortem:
one broken stage aborted the run before later stages executed, blanking
their coverage from the driver artifact)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _run_stages  # noqa: E402


def test_one_failing_stage_does_not_blank_the_rest(capsys):
    ran = []

    def ok(name):
        def fn():
            ran.append(name)
            return None

        return fn

    def boom():
        ran.append("boom")
        raise ValueError("injected")

    def skipped():
        ran.append("skipped")
        return "skipped (reason)"

    failures = _run_stages(
        [("a", ok("a")), ("boom", boom), ("b", ok("b")), ("s", skipped)],
        "cpu x8",
    )
    # Every stage ran despite the injected failure in the second.
    assert ran == ["a", "boom", "b", "skipped"]
    assert [name for name, _ in failures] == ["boom"]
    assert isinstance(failures[0][1], ValueError)
    out = capsys.readouterr().out
    assert "[dryrun cpu x8] a: PASS" in out
    assert "[dryrun cpu x8] boom: FAIL (ValueError: injected)" in out
    assert "[dryrun cpu x8] b: PASS" in out
    assert "[dryrun cpu x8] s: skipped (reason)" in out


def test_all_green_returns_no_failures():
    assert _run_stages(
        [("a", lambda: None), ("b", lambda: None)], "cpu x8"
    ) == []
