"""Plain references: the same semantics in straightforward NumPy and
float64, sharing no code with the program. They decide `correct`."""
