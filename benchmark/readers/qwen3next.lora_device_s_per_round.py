"""``lora.device_s_per_round`` in the Qwen3-Next cell: device self
seconds a round of the adapters' side paths (``lora.side``) in the round
program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "lora.side")
