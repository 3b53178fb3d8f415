"""Plain float32 references and work counts, one module per architecture
family.  They import nothing of the program under test."""
