"""Checks of the work functions and the peaks table (``work.py``), and of
the Mamba-2 training FLOPs its reference module gives the ``mfu.*``
readers.

    python -m pytest -q bench/test_work.py
"""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from drive import load_module  # noqa: E402

W = load_module(HERE / "work.py", "bench_work")
M = load_module(HERE / "models" / "mamba2.py", "bench_ref_mamba2")

#: the reduced Mamba-2 the CPU tests train: 4 layers, d_model 64, 4 heads
#: of 16, state 16, chunk 16, vocabulary 512 (already a multiple of 16)
SMALL = dict(d_model=64, n_layer=4, d_state=16, nheads=4, headdim=16,
             d_conv=4, chunk_size=16, vocab_size=512,
             pad_vocab_size_multiple=16, seq=64)


def test_mamba2_flops_by_hand():
    # one layer, per token: di = 64, E = 2*64 + 2*16 + 4 = 164
    in_proj = 2 * 64 * 164            # 20,992
    out_proj = 2 * 64 * 64            # 8,192
    scores = 2 * 16 * 16              # C B^T in the chunk: 512
    intra = 2 * 16 * 4 * 16           # decay-weighted sum: 2,048
    states = 2 * (2 * 4 * 16 * 16)    # end state + inter-chunk output: 4,096
    conv = 2 * 4 * (64 + 32)          # 768
    layer = in_proj + out_proj + scores + intra + states + conv
    assert layer == 36_608
    head = 2 * 64 * 512               # tied output projection: 65,536
    forward = 4 * layer + head        # 211,968
    assert M.train_flops_per_token(SMALL) == 3 * forward == 635_904


def test_chunk_is_capped_by_the_sequence():
    short = dict(SMALL, seq=8)
    assert M.train_flops_per_token(short) < \
        M.train_flops_per_token(SMALL)


def test_published_size_is_in_the_expected_range():
    cfg = json.loads((HERE / "configs" / "mamba2-130m.async.json").read_text())
    per_token = M.train_flops_per_token(cfg)
    # about 3 x 2 x (params ~ 130 M, embedding included), plus the scan
    assert 0.8e9 < per_token < 1.0e9
    assert per_token == 891_297_792


def test_scan_bytes_of_one_leaf():
    # the stacked w_in's Adam moment: (24, 768, 3352) float32
    nbytes = 24 * 768 * 3352 * 4
    assert nbytes == 247_136_256
    blocks = nbytes // 4096           # 60,336 blocks of 4 KiB, all dirty
    assert W.save_scan_bytes([nbytes], blocks) == 3 * nbytes
    assert W.save_scan_bytes([nbytes], 0) == 2 * nbytes
    pages = -(-nbytes // (128 * 1024))   # 1,886 pages, the last one half
    assert pages == 1886
    assert W.restore_scan_bytes([nbytes], 128 * 1024) == 2 * pages * 131072


def test_known_and_unknown_devices():
    assert W.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        W.peaks("TPU v9 imaginary")
