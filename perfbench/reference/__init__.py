"""Plain references that decide ``correct``. They import nothing of the
program and read only the weights and inputs the benchmark made."""
