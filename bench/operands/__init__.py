"""One module per operand kind: the operands of a call, their float64
reference, the control and the bytes a row. Found by name from a
configuration's ``operands.kind`` (``Manifest.operands``)."""
