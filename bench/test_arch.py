"""The harness knows no architecture: each configuration's reference
module (``models/<reference>.py``) tells it, in one table (``PROGRAM``),
what the program's model is and what to hold the built model to, and
gives the training FLOPs per token and the CPU rehearsal's sizes.

Shows that the Mamba-2 files build the model and compare the keys they
always did, that a planted mismatch is refused, that no Mamba-2 key is
left in the generic harness, and that a second architecture enters
through a module and a file of its own, editing nothing.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_arch.py
"""

import dataclasses
import json
import re
import types

import pytest

from conftest import HERE, ROOT

import drive  # noqa: E402

CONFIGS = {"async": "mamba2-130m.async", "sync": "mamba2-130m.sync"}


def _file(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _built(name):
    from repro.configs import get_config

    return get_config(drive.program_arch(_file(name)))


def _trainer(cfg, model):
    """What ``check_program_matches`` reads of a ``Trainer``, with the
    configuration's deployment."""
    dep = cfg["deployment"]
    flusher = None
    if dep["async_flush"]:
        flusher = types.SimpleNamespace(_queues=[types.SimpleNamespace(
            maxsize=dep["max_pending"])])
    manager = types.SimpleNamespace(cfg=types.SimpleNamespace(
        page_size=dep["page_size"],
        manifest_capacity=dep["manifest_capacity"]))
    return types.SimpleNamespace(cfg=model, manager=manager, flusher=flusher)


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_mamba2_files_build_the_same_model(kind):
    from repro.configs import get_config

    name = CONFIGS[kind]
    # the model the harness registered before the architecture's keys
    # moved into models/mamba2.py
    want = dataclasses.replace(
        get_config("mamba2-130m"), name=name, num_layers=24, d_model=768,
        ssm_state=128, ssm_heads=24, ssm_head_dim=64, expand=2,
        conv_kernel=4, chunk=256, vocab_size=50277, vocab_pad=16,
        tp_heads_multiple=1, tie_embeddings=True, norm_eps=1e-6,
        dtype="bfloat16")
    assert _built(name) == want


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_check_compares_the_same_keys_and_values(kind):
    name = CONFIGS[kind]
    cfg = _file(name)
    compared = drive.check_program_matches(_trainer(cfg, _built(name)), cfg)
    assert compared == {
        "d_model": 768, "n_layer": 24, "d_state": 128, "headdim": 64,
        "nheads": 24, "expand": 2, "d_conv": 4, "chunk_size": 256,
        "vocab_size": 50277, "padded_vocab": 50288, "dtype": "bfloat16",
        "rms_norm_eps": 1e-6, "tie_embeddings": True, "page_size": 131072,
        "manifest_capacity": 16777216, "async_flush": kind == "async",
        "max_pending": 2 if kind == "async" else None}


@pytest.mark.parametrize("change,key", [
    (dict(ssm_state=64), "d_state"),
    (dict(ssm_head_dim=32), "headdim"),
    (dict(tp_heads_multiple=16), "nheads"),      # 24 heads padded to 32
    (dict(vocab_pad=256), "padded_vocab"),
    (dict(num_layers=12), "n_layer"),
])
def test_check_refuses_a_planted_mismatch(change, key):
    cfg = _file(CONFIGS["async"])
    model = dataclasses.replace(_built(CONFIGS["async"]), **change)
    with pytest.raises(RuntimeError, match=f"'{key}'"):
        drive.check_program_matches(_trainer(cfg, model), cfg)


def test_no_mamba2_key_in_the_generic_harness():
    m2 = drive.reference_model(_file(CONFIGS["async"]))
    cfg = _file(CONFIGS["async"])
    generic = {"vocab_size", "vocab_pad", "dtype"}
    keys = (set(m2.REDUCED) | {k for k, _ in m2.PROGRAM.values()}) - generic
    attrs = {a for _, a in m2.PROGRAM.values() if a} | set(m2.PROGRAM)
    files = [HERE / "drive.py", HERE / "work.py", HERE / "conftest.py",
             *sorted((HERE / "metrics").glob("*.py"))]
    for f in files:
        text = f.read_text()
        for k in keys:
            assert not re.search(rf"[\"']{k}[\"']", text), (f.name, k)
        for a in attrs - generic:
            assert not re.search(rf"\.{a}\b", text), (f.name, a)


#: a second architecture's reference module, as a configuration of it would
#: bring: the program's reduced phi3.5-moe, its keys named as in its
#: published config.json
STUB = '''
REDUCED = {}


PROGRAM = {
    "num_layers": ("num_hidden_layers", "num_layers"),
    "d_model": ("hidden_size", "d_model"),
    "num_heads": ("num_attention_heads", "padded_heads"),
    "num_kv_heads": ("num_key_value_heads", "padded_kv_heads"),
    "head_dim": ("head_dim", "raw_head_dim"),
    "d_ff": ("intermediate_size", None),
    "moe_d_ff": ("intermediate_size", "moe_d_ff"),
    "num_experts": ("num_local_experts", "num_experts"),
    "top_k": ("num_experts_per_tok", "top_k"),
    "vocab_size": ("vocab_size", "vocab_size"),
    "vocab_pad": ("vocab_pad", None),
}


def train_flops_per_token(cfg):
    return 1.0
'''


def test_second_architecture_enters_through_new_files(tmp_path, monkeypatch):
    from repro.configs import get_config

    (tmp_path / "phi_moe_stub.py").write_text(STUB)
    monkeypatch.setattr(drive, "MODELS", tmp_path)
    cfg = dict(
        _file(CONFIGS["sync"]), arch="phi3.5-moe-42b-a6.6b",
        reference="phi_moe_stub", name="phi35-moe.stub",
        num_hidden_layers=4, hidden_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, intermediate_size=256,
        num_local_experts=4, num_experts_per_tok=2, vocab_size=512,
        vocab_pad=16, batch=2, seq=64)
    model = get_config(drive.program_arch(cfg))
    assert (model.family, model.num_layers, model.d_model) == ("moe", 4, 128)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (4, 2, 32)
    assert (model.num_experts, model.top_k, model.moe_d_ff) == (4, 2, 256)
    assert model.padded_vocab == 512 and model.tp_heads_multiple == 1

    traffic = {"ckpt_every": 8, "checked_steps": 3,
               "tokens": {"zipf_a": 1.3, "uniform_share": 0.5}}
    d = drive.Driver(cfg, traffic, 1, 0.0, tmp_path / "run", t_start=0.0)
    trainer = d.new_trainer()
    compared = drive.check_program_matches(trainer, cfg)
    assert compared["intermediate_size"] == 256
    assert compared["num_key_value_heads"] == 2
    for key, other in [("intermediate_size", 512), ("num_key_value_heads", 4),
                       ("num_local_experts", 8), ("head_dim", 64)]:
        with pytest.raises(RuntimeError, match=f"'{key}'"):
            drive.check_program_matches(trainer, dict(cfg, **{key: other}))
