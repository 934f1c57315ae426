"""Plain references that decide `correct`.  They import nothing of the
program under test and make their weights and data from the seed."""
