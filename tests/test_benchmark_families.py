"""The benchmark's family files against the program, in tier-1: the
interface check, ``tiny`` and the rehearsal of EVERY cell through its family
(``benchmarks/tests/test_families.py``'s cases, run here as they are), and
the stand-in family that comes as files and entries only.  A program PR that
renames ``serve_decode_fns`` or a ``Config`` field, or changes what the
engine hands a step, fails here and not in a chip run.

The cases are the benchmark's own functions, imported: each parametrised
case counts and none is written twice.

Since PR 36 also ``benchmarks/tests/test_window_readers.py``'s cases: the
readers of the engine's own record and the rehearsal of every serve cell that
names their metrics - a program PR that renames ``decode/itl_ms``,
``decode/host/ns`` or ``decode_reads_ready``, or moves a span the join reads,
fails here too.  In THIS file because a rehearsal loads every core: pytest
hands a file to one worker, so these run after the cases above and not beside
them (the planted-weights cases below sample what finishes in three seconds).
"""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def _cases_of(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{name}",
        os.path.join(ROOT, "benchmarks", "tests", f"{name}.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_cases = _cases_of("test_families")

test_every_family_file_has_its_paths_whole_interface = (
    _cases.test_every_family_file_has_its_paths_whole_interface)
test_tiny_keeps_the_configuration_and_states_the_rehearsals_limits = (
    _cases.test_tiny_keeps_the_configuration_and_states_the_rehearsals_limits)
test_every_cell_rehearses_correct_through_its_family = (
    _cases.test_every_cell_rehearses_correct_through_its_family)
test_a_new_family_is_files_and_entries_only = (
    _cases.test_a_new_family_is_files_and_entries_only)

_window = _cases_of("test_window_readers")
globals().update({
    name: case for name, case in vars(_window).items() if name.startswith("test_")
})


def test_the_fourteen_metrics_are_entries_and_files_of_their_families(monkeypatch):
    """PR 36's case, which looks for its fourteen entries at the very END of
    ``per_layer``, on the manifest cut off after the last of them: a later
    PR appends its own there (PR 38: two; PR 39: three), as ``BENCHMARK.json``
    asks, and with each list of ``workloads`` cut back to the six cells of
    its day: a later configuration's cell is appended to the lists of the
    metrics it reports (PR 39: ``trinity-mini-serve-mixed``).
    ``benchmarks/tests`` is not a program PR's to edit."""
    entries = _window.M["per_layer"]
    last = max(i for i, p in enumerate(entries) if p["name"] in _window.NEW)
    of_its_day = [w["name"] for w in _window.M["workloads"][:6]]
    monkeypatch.setitem(_window.M, "per_layer", [
        dict(p, workloads=[w for w in p["workloads"] if w in of_its_day])
        for p in entries[:last + 1]])
    _window.test_the_fourteen_metrics_are_entries_and_files_of_their_families()


def test_an_altered_selection_is_caught_by_the_serve_cells_comparison(monkeypatch):
    """The control of the served tokens, where the token is picked since
    PR 30: the engine's compiled step made to select the index after the
    best one has to come out of a serve cell's comparison as not correct.
    (``benchmarks/tests/test_control.py::test_an_altered_token_is_caught``
    alters the host's ``np.argmax``, which the engine no longer calls.)"""
    import time

    from benchmarks import rehearse
    from benchmarks.harness import manifest, serve_cell
    from distributed_tensorflow_examples_tpu.serve import model_server

    selecting = model_server._selecting

    def altered(model_step):
        step = selecting(model_step)

        def step_fn(*args):
            selection, cache = step(*args)
            return (selection + 1) % 250, cache

        return step_fn

    monkeypatch.setattr(model_server, "_selecting", altered)
    cell = rehearse.shrink(manifest.Cell("cgpt13b-serve-batch"))
    out = serve_cell.run(cell, 4, 3.0, False, time.monotonic())
    assert out["check"]["positions"] > 0 and out["failed"] == 0
    assert out["correct"] is False


@pytest.mark.parametrize("roll", [r"layer_\d+/moe/down", r"layer_1/moe/down"])
def test_mixed_up_expert_weights_are_caught_by_the_deepseek_cells_comparison(roll):
    """The control of the ROUTED part, which weighs a fifth of the shared
    experts' in this cell's logits (its configuration's ``assumed``): the
    rehearsal served with every held expert computing with its neighbour's
    matrix (``benchmarks/tools/planted.py``: the matched leaves rolled by
    one along the experts' axis) - ``down`` in both expert layers, or in
    one - has to come out of the cell's comparison as not correct.  At this
    size the sound program reads 0.001-0.025 and these 0.25-0.37 over three
    seeds each against the limit of 0.1 (``gate`` mixed up in ONE layer
    reads 0.10-0.19, too near the limit to hold a test); what the real size
    reads is in ``PERF.md`` section 2."""
    import time

    from benchmarks import rehearse
    from benchmarks.harness import manifest, serve_cell
    from benchmarks.tools import planted

    cell = rehearse.shrink(manifest.Cell("deepseek-v2-serve-gen"))
    family, build, seen = cell.family, cell.family.build, []

    def build_planted(config, *a, **kw):
        cfg, tree_fn = build(config, *a, **kw)
        return cfg, planted.rolled(tree_fn, roll, seen)

    family.build = build_planted
    try:
        out = serve_cell.run(cell, 5, 3.0, False, time.monotonic())
    finally:
        family.build = build
    assert seen and out["check"]["positions"] > 0 and out["failed"] == 0
    assert out["correct"] is False
