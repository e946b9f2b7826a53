"""Plain reference queue the benchmark checks the program against."""
