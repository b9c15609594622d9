"""Bonito's released CTC basecaller for R9.4.1 DNA (nanoporetech/bonito
v0.3.x, ``bonito/models/configs/dna_r9.4.1``), at its published widths.

QuartzNet-style: 8 blocks of time-channel separable convs (a depthwise
conv, then a 1x1 conv) or full convs, each followed by BatchNorm (eps
1e-3) and swish, except after a block's last conv; blocks 1-5 add a
1x1 conv + BatchNorm of their input before the block's swish. The
decoder is a 1x1 conv with bias, 48 -> 5, then a log-softmax. 6.6 M
parameters, receptive field 3,237 samples, stride 3.

The table is transcribed from the released config as recalled, not
copied from the file (no copy is at hand): see ``bench/configs/
bonito.json``'s ``assumed``.
"""
from repro.config import ModelConfig, register

CONFIG = register(ModelConfig(
    name="bonito-r9.4.1",
    family="basecaller",
    n_layers=8,
    d_model=464,
    n_blocks=8,
    channels=(344, 424, 464, 456, 440, 280, 384, 48),
    kernel_sizes=(9, 115, 5, 123, 9, 31, 67, 15),
    strides=(3, 1, 1, 1, 1, 1, 1, 1),
    repeats=(1, 2, 7, 4, 9, 6, 1, 1),
    separable=(False, True, True, True, True, True, True, False),
    residual=(False, True, True, True, True, True, False, False),
    use_skips=True,
    activation="swish",
    norm_eps=1e-3,
    head_bias=True,
    n_bases=5,
    vocab_size=5,
    source="github.com/nanoporetech/bonito v0.3.x, "
           "bonito/models/configs/dna_r9.4.1",
))
