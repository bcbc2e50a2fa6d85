"""repro: TPU-native reproduction of GPU Multisplit (see ROADMAP.md)."""
