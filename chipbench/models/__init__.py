"""One module per model family: how the program's model is built from a
configuration file, what one sample costs in FLOPs, and how the system's
weights are handed to the family's plain reference."""
