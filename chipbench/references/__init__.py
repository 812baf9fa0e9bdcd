"""Reference implementations the check compares the program with."""
