"""Plain references that decide ``correct``. They import nothing of the
program under test and take nothing it made: weights come from the
benchmark's own seeded maker, and the product tables are rebuilt here from
the paper's cell equations."""
