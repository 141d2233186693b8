"""The ``phi3-mini-3l`` configuration file against the published widths of
Phi-3-mini (``microsoft/Phi-3-mini-4k-instruct``)."""

from harness import spec


def test_phi3_mini_3l_holds_the_published_widths():
    cfg = spec.load_cell("phi3-mini-3l.periodic").config
    assert cfg["hidden_size"] == 3072 and cfg["intermediate_size"] == 8192
    assert cfg["num_attention_heads"] == 32
    assert cfg["vocab_size"] == 32064
