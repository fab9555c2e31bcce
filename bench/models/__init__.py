"""Models a training cell drives, each with its plain reference."""
