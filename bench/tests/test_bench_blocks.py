"""A block is found by name: a block module written as a new file under
a directory the lookup searches runs the tiny cell end to end, its own
reference decides ``correct``, and a name with no file fails before any
weight is made."""
from pathlib import Path

import pytest

import tiny
from bench import blocks, weights

DENSE = (Path(blocks.__file__).parent / "dense.py").read_text()

# a fault planted in the copy's reference alone: rotary theta 10
ROTARY_THETA_10 = """

_sound_rope = _rope


def _rope(x, pos, theta):
    return _sound_rope(x, pos, 10.0)
"""


@pytest.fixture
def new_block(tmp_path, monkeypatch):
    """Write ``<name>.py`` into a directory the lookup searches."""
    monkeypatch.setattr(blocks, "DIRS", blocks.DIRS + (tmp_path,))

    def write(name, text):
        (tmp_path / f"{name}.py").write_text(text)
        return tiny.conf() | {"block": name}
    return write


@pytest.mark.parametrize("source,correct", [
    (DENSE, True),
    (DENSE + ROTARY_THETA_10, False),
], ids=["copy_of_dense", "rotary_theta_10"])
def test_a_block_written_as_a_new_file_runs_the_tiny_cell(
        new_block, source, correct):
    conf = new_block("tinyblock", source)
    assert "tinyblock" in blocks.names()
    assert blocks.of(conf).__file__ != blocks.of({}).__file__
    r = tiny.run(trace=True, seed=2 ** 32 + 41, conf=conf)
    assert r["correct"] == correct, r["checks"]
    gap = r["checks"]["logit_gap"]
    assert (gap["value"] <= gap["limit"]) == correct
    assert r["metrics"]["decode.mfu"]["value"] > 0     # its counts are read


def test_an_unknown_block_fails_before_any_weight(monkeypatch):
    drawn = []
    monkeypatch.setattr(weights, "base_key",
                        lambda seed: drawn.append(seed))
    with pytest.raises(LookupError, match="the blocks are: dense"):
        tiny.run(conf=tiny.conf() | {"block": "no_such_block"})
    assert drawn == []


def test_a_block_lacking_a_function_is_refused(new_block):
    conf = new_block("partial", DENSE.replace("def decode_flops(",
                                              "def _decode_flops("))
    with pytest.raises(LookupError, match="lacks decode_flops"):
        blocks.of(conf)
