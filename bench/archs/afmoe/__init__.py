"""AFMoE (Arcee Trinity): window and full attention layers in a fixed
pattern, QK-norm and a sigmoid output gate, sandwich norms, leading dense
layers and then sparse experts with sigmoid routing, a selection bias and
one shared expert; the chip holds a share of each layer's experts."""
ARCHITECTURES = ("AfmoeForCausalLM",)
