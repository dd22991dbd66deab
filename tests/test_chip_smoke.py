"""``chip_smoke.py`` off the chip: its control flow, the shape of its last
line, its refusals, and the rule that a launcher's parent stays off JAX.

What only the chip can show — the phases themselves — is run there
(``python chip_smoke.py`` through the chip tool) and, at a tiny size on
the CPU backend, by ``python chip_smoke.py --rehearse`` (minutes: it
compiles the recover graph).  These tests take seconds: the phases are
stubbed in a child interpreter, because the parent under test must be a
process that never imported JAX and the pytest process already has.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _python(code: str, *, cwd=REPO, env=None, timeout=120):
    env = dict(os.environ if env is None else env, PYTHONPATH=REPO)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_launcher_parents_never_import_jax():
    """A parent that touched JAX would hold the chip its child needs:
    every module that starts a chip-holding child imports clean."""
    proc = _python("""
        import sys
        import chip_smoke, harness.cluster
        import eges_tpu.node.service, eges_tpu.crypto.aotstore
        bad = sorted(m for m in sys.modules if m == "jax"
                     or m.startswith(("jax.", "jaxlib")))
        assert not bad, bad
    """)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_last_line_is_exactly_the_contract():
    import chip_smoke

    line = chip_smoke.final_line(dict(TPU, extra="dropped"))
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(line) == {"ok": True, "device": TPU}


def _stubbed_main(argv, *, child="ok", device=TPU, off_chip=()):
    """Run ``chip_smoke.main(argv)`` in a fresh interpreter with the
    phases replaced: ``child`` is ``ok`` or ``raises``."""
    return _python(f"""
        import json, sys
        import chip_smoke
        from eges_tpu.crypto import native
        native.ensure_built = lambda: {os.path.join(REPO, "native", "x.so")!r}
        ran = []
        def run_child(phase, args, size):
            ran.append(phase)
            if {child!r} == "raises":
                raise RuntimeError("phase failed (rc=1)")
            return {{"device": {dict(device)!r},
                     "off_chip": {list(off_chip)!r}}}
        def phase_served(seed, size, rehearse):
            ran.append("served")
            return {{"off_chip": []}}
        chip_smoke.run_child = run_child
        chip_smoke.phase_served = phase_served
        rc = chip_smoke.main({list(argv)!r})
        print("RAN " + json.dumps(ran), file=sys.stderr)
        sys.exit(rc)
    """)


def _ok_lines(stdout: str):
    return [ln for ln in stdout.splitlines() if '"ok": true' in ln]


def test_all_phases_passing_ends_in_the_ok_line_and_exit_0():
    proc = _stubbed_main([])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert all(isinstance(json.loads(ln), dict) for ln in lines)
    assert json.loads(lines[-1]) == {"ok": True, "device": TPU}
    assert _ok_lines(proc.stdout) == [lines[-1]]
    assert 'RAN ["verifier", "served"]' in proc.stderr


def test_chips_4_runs_the_mesh_phase_and_no_other():
    four = dict(TPU, count=4)
    proc = _stubbed_main(["--chips", "4"], device=four)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert 'RAN ["mesh"]' in proc.stderr
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": four}


@pytest.mark.parametrize("kw", [
    {"child": "raises"},                        # a phase failed
    {"device": {"platform": "cpu", "kind": "cpu", "count": 1}},
    {"off_chip": ["platform_is_tpu"]},          # a rehearsal's summary
    {"device": dict(TPU, count=4)},             # wrong number of chips
], ids=["phase-raises", "cpu-device", "off-chip-recorded", "chip-count"])
def test_anything_short_of_a_pass_exits_nonzero_without_ok(kw):
    proc = _stubbed_main([], **kw)
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout)


def test_without_an_accelerator_the_real_script_fails_fast():
    """As the driver runs it in the sandbox: JAX finds no accelerator,
    the verifier child refuses at its first check, nothing says ok."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout)
    assert '"check": "platform_is_tpu", "holds": false' in proc.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env={k: v for k, v in os.environ.items()
                               if k != "PYTHONPATH"},
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout)


def test_checks_raise_except_device_path_ones_in_a_rehearsal(capsys):
    import chip_smoke

    strict = chip_smoke.Checks("verifier", rehearse=False)
    strict("anything", True)
    with pytest.raises(AssertionError):
        strict("platform_is_tpu", False)
    rehearsal = chip_smoke.Checks("verifier", rehearse=True)
    rehearsal("platform_is_tpu", False)
    assert rehearsal.off_chip == ["platform_is_tpu"]
    with pytest.raises(AssertionError):
        rehearsal("windows_equal_native_row_for_row", False)
    assert all(json.loads(ln)["phase"] == "verifier"
               for ln in capsys.readouterr().out.splitlines())


def test_seeded_rows_carry_the_four_invalid_kinds():
    """One row in eight is invalid — s out of range, bad v, flipped
    hash byte, r off the curve, in turn — the same rows for the same
    seed, and the native reference says which recover."""
    import chip_smoke

    sigs, hashes = chip_smoke.seeded_rows(7, 64)
    again = chip_smoke.seeded_rows(7, 64)
    assert (sigs == again[0]).all() and (hashes == again[1]).all()
    assert len({bytes(r) for r in sigs}) == 64
    addrs, ok = chip_smoke.native_reference(sigs, hashes)
    bad = np.arange(7, 64, 8)
    kinds = (bad // 8) % 4
    # a flipped hash byte still recovers (another address); the rest don't
    assert ok[bad[kinds == 2]].all() and not ok[bad[kinds != 2]].any()
    assert ok[np.setdiff1d(np.arange(64), bad)].all()
    assert not addrs[~ok].any()
    clean, _ = chip_smoke.native_reference(*chip_smoke.seeded_rows(7, 7))
    assert (clean == addrs[:7]).all()
