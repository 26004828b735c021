"""Temporary bytes of the compiled training step the window runs
(``memory_analysis().temp_size_in_bytes``, the chip compiler's own
figure): where the ACT residuals and the model's intermediates live."""


def read(ctx: dict):
    temp = ctx.get("temp_bytes")
    return None if temp is None else temp / 1e9
