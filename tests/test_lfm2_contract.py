"""``tiny-lfm2`` as a case of the served contract (``model_contract.py``);
its own mechanisms are ``test_lfm2.py``'s."""

from model_contract import Case, contract_of

CASE = Case(
    tiny="tiny-lfm2",
    # the cached K, V and gated inputs alone in int8 move this toy less
    controls=(("bf16", 3), ("int8", 3), ("fp8", 3), ("kv_int8", 1)),
    # read and written: 8 layers x 2 gated inputs x 128 channels, float32
    row_bytes=2 * 8 * 2 * 128 * 4,
    # At 128 channels ``W_in``'s outputs are 0.02 sqrt(128) = 0.23 where
    # the published 2048 give 0.9, and the mixer's output, a product of
    # three of them, is 60 times smaller beside the embedding: scaled back,
    # so that the convolution weighs in these logits as it does deployed.
    scaled=(("conv_mixers", "conv_in", 4.0),))

globals().update(contract_of(CASE))
