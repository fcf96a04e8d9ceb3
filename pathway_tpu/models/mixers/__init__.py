"""The decoder's layer kinds, one module each (global and window attention
share `softmax`), behind models/layers.py `Kind`; models/transformer.py
`KINDS` is their table."""
