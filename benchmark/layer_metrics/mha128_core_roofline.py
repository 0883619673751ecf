"""The attention core of heads 128 wide, one query head a KV head,
against the chip's bf16 peak: 2 x heads x 256 FLOPs a kept (query, key)
pair forward, three times that trained, every layer of every loop
(``flops_ouro.attention_core_train_flops``), over the device time under
the ``attn_core`` scope, every pass (``loop_scopes.core_roofline_share``)."""

from benchmark import loop_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return loop_scopes.core_roofline_share(record)
