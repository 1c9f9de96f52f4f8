"""A training run's ``correct`` comes out false when the timed path is
broken underneath: a step that returns its state unchanged, half of the
batch left out with the mean taken over the rest, or the window's ``train``
call starting afresh instead of from the state handed to it. A sound run
passes."""
import pytest

import tinycell

CELLS = ["dbn-baidu.train", "dctr-baidu.train"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path):
    record = tinycell.run(tinycell.tiny_cell(name, tmp_path))
    assert record.attempted > 0 and record.failed == 0
    assert all(c.ok for c in record.checks), record.checks
    assert record.end_to_end["train_sessions_per_s"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged_is_caught(name, tmp_path, monkeypatch):
    from repro.train.engine import TrainEngine

    monkeypatch.setattr(TrainEngine, "_apply_update",
                        lambda self, params, opt_state, grads, batch:
                        (params, opt_state))
    record = tinycell.run(tinycell.tiny_cell(name, tmp_path))
    bad = {c.name for c in record.checks if not c.ok}
    assert "step" in bad, record.checks


@pytest.mark.parametrize("name", CELLS)
def test_half_batch_is_caught(name, tmp_path, monkeypatch):
    from repro.core import MODEL_REGISTRY

    cell = tinycell.tiny_cell(name, tmp_path)
    cls = MODEL_REGISTRY[cell.config["model"]]
    whole = cls.compute_loss

    def half(self, params, batch):
        rows = batch["clicks"].shape[0] // 2
        return whole(self, params, {k: v[:rows] for k, v in batch.items()})

    monkeypatch.setattr(cls, "compute_loss", half)
    record = tinycell.run(cell)
    assert not all(c.ok for c in record.checks), record.checks


@pytest.mark.parametrize("name", CELLS)
def test_window_call_that_drops_its_state_is_caught(name, tmp_path,
                                                    monkeypatch):
    from repro.train import Trainer

    whole = Trainer.train

    def afresh(self, model, loader, *args, state=None, **kwargs):
        return whole(self, model, loader, *args, **kwargs)  # state dropped

    monkeypatch.setattr(Trainer, "train", afresh)
    record = tinycell.run(tinycell.tiny_cell(name, tmp_path))
    bad = {c.name for c in record.checks if not c.ok}
    assert "loss" in bad, record.checks
