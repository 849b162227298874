"""Model zoo (benchmark/fluid/models + tests/book model roles)."""

from . import (
    bert,
    ctr_deepfm,
    gpt2,
    machine_translation,
    mnist,
    olmoe,
    resnet,
    se_resnext,
    sentiment,
    stacked_dynamic_lstm,
    transformer,
    vgg,
    word2vec,
)
