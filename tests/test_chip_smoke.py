"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes: each phase's
comparison with the NumPy oracle passes, and the script refuses to run
without a TPU.  (The full-size run needs the chip.)"""
import chip_smoke


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out
    assert "[device] platform=cpu" in out and '"ok"' not in out


def test_consult_phase_matches_oracle():
    assert chip_smoke.phase_consult(128) == []


def test_event_replay_phase_matches_oracle():
    assert chip_smoke.phase_events(24, 80) == []


def test_hadare_phase_matches_oracle():
    assert chip_smoke.phase_hadare(8, 120) == []


def test_pallas_phase_matches_reference():
    assert chip_smoke.phase_pallas(
        attn=dict(hq=4, hkv=2, dh=64, seq=256), wkv=dict(h=2, d=64, seq=64),
        norm=dict(rows=16, d=256)) == []
