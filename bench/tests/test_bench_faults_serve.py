"""A serving run's ``correct`` comes out false when an answer is altered
where it is produced. A sound run passes."""
import numpy as np

import tinycell

NAME = "dbn-baidu.serve-poisson"


def test_sound_run_is_correct(tmp_path):
    record = tinycell.run(tinycell.tiny_cell(NAME, tmp_path, seconds=1.0))
    assert record.attempted == 300
    assert all(c.ok for c in record.checks), record.checks
    tails = record.unbounded
    assert tails["serve_p99_ms"]["value"] >= tails["serve_p95_ms"]["value"] \
        >= record.end_to_end["serve_p50_ms"]


def test_altered_answer_is_caught(tmp_path, monkeypatch):
    from repro.serve.registry import ModelEntry

    cell = tinycell.tiny_cell(NAME, tmp_path, seconds=1.0)
    honest = ModelEntry.run

    def altered(self, tier, batch):
        out = np.array(honest(self, tier, batch))
        # one position of one answer, off by twice the limit
        out[0, 3] += np.log1p(2 * cell.limits["click_prob"])
        return out

    monkeypatch.setattr(ModelEntry, "run", altered)
    record = tinycell.run(cell)
    bad = {c.name for c in record.checks if not c.ok}
    assert bad == {"click_prob"}, record.checks
