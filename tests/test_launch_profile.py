"""The training launcher's device profile (``--profile-dir``): the
trace holds the loop's host spans, the compiled step's text beside it
holds the step's named scopes (DESIGN.md §16); the history records the
per-segment outcome counters and no host clock."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes, trace  # noqa: E402
from repro.launch import train  # noqa: E402

SMOKE = ["--arch", "stablelm-1.6b", "--smoke", "--mesh", "1x1",
         "--batch", "2", "--seq", "16", "--log-every", "1",
         "--density-policy", "none", "--backend", "reference"]


def _segments(arch: str, ratio: float) -> int:
    """Leaf segments of the launcher's bucket layout on one chip."""
    import jax

    from repro.configs import get_config
    from repro.core.compressors import get_compressor
    from repro.dist.layout import build_layout
    from repro.models import init_params

    cfg = get_config(arch).reduced()
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return len(build_layout(shapes, 1, ratio,
                            get_compressor("gaussiank")).segments)


def test_profile_needs_a_step_after_the_warm_up(tmp_path):
    with pytest.raises(SystemExit, match="--steps > 2"):
        train.train(SMOKE + ["--steps", "2", "--profile-dir",
                             str(tmp_path)])


def test_profile_holds_spans_and_scopes(tmp_path, capsys):
    history = train.train(SMOKE + ["--steps", "3",
                                   "--profile-dir", str(tmp_path)])
    assert [sorted(r) for r in history] == [
        ["comm_frac", "ef_leaves_at_cap", "ef_leaves_under_band", "loss",
         "step"]] * 3
    segments = _segments("stablelm-1.6b", ratio=0.001)
    for r in history:
        for c in ("ef_leaves_at_cap", "ef_leaves_under_band"):
            assert r[c] == int(r[c]) and 0 <= r[c] <= segments, (c, r[c])
    out = capsys.readouterr().out
    assert out.count(" at_cap=") == 3 and out.count(" under_band=") == 3
    pd = trace.load(trace.find_xplane(str(tmp_path)))
    spans = {ev.name for p in pd.planes if p.name.startswith("/host")
             for line in p.lines for ev in line.events}
    assert {"train.input", "train.dispatch", "train.sync"} <= spans
    text = (tmp_path / "step.hlo.txt").read_text()
    assert set(scopes.hlo_scopes(text).labels.values()) == (
        set(scopes.LABELS) - {scopes.OTHER})
    # a CPU trace has no TPU plane: the reduction refuses it rather
    # than read no time as zero (tests/test_scopes.py reduces a
    # synthetic TPU plane)
    with pytest.raises(SystemExit, match="no /device:TPU plane"):
        scopes.main([str(tmp_path), "--steps", "1"])
