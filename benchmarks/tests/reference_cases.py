"""Seeded tiny cases of the benchmark's references, one a reference: the
cell's own configuration shrunk by ``rehearse.tiny``, the program's seeded
weights under the reference's names, a few rows of tokens. ``test_reference``
holds each reference's unrounded run and its twin on them."""

import numpy as np

from benchmarks import rehearse
from benchmarks.harness import serve_cell, spec

#: reference -> a cell whose configuration names it
CELLS = {"reference.py": "gpt2-124m.serve-decode",
         "references/deepseek_v3.py": "kanana-2-30b-a3b.serve-long-decode",
         "references/minicpm_sala.py": "minicpm-sala.serve-long-context"}
SEED = 2_500_000_003


def case(reference_file: str, n_layer: int = 0, rows: int = 2, t: int = 64):
    """-> (the reference's module, weights, tokens (rows, t), sizes)."""
    sizes = {"n_layer": n_layer} if n_layer else None
    cell = rehearse.tiny(spec.load_cell(CELLS[reference_file]), sizes=sizes)
    cfg = spec.gpt_config(cell, training=False)
    reference = spec.load_reference(cell.config)
    assert cell.config["reference"] == reference_file
    weights = reference.weights_from_program(
        serve_cell.init_params(cfg, SEED))
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(rows, t), dtype=np.int32)
    return reference, weights, tokens, cell.config
